"""The timed path broken underneath: ``correct`` comes out false.

A served model can have one of the contract's faults: a token altered
where it is produced.  Here the engine's sampler returns the next id
after the one it chose, on both engines."""

import time

import pytest

from chipbench.bench import load_cell
from chipbench.harness import run_cell
from chipbench.tests.tiny import make_bench, on_cpu


@pytest.mark.parametrize("engine", ["slot", "continuous"])
def test_altered_tokens_are_not_correct(tmp_path, monkeypatch, engine):
    import repro.serving.engine as eng

    on_cpu(monkeypatch)
    sample = eng.sample_tokens
    monkeypatch.setattr(
        eng, "sample_tokens",
        lambda logits, *a: (sample(logits, *a) + 1) % logits.shape[-1])
    cell = load_cell(make_bench(tmp_path, engine=engine), root=tmp_path,
                     bench_dir=tmp_path / "chipbench")
    res = run_cell(cell, seed=11, seconds=1.5, trace=False,
                   t0=time.perf_counter())
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]
