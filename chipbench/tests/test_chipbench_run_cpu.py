"""A mix added as a file only is run end to end, with the harness's look
for a chip skipped: the line carries every end-to-end metric and the
checks, the served tokens pass the reference, and the control (the
reference at int4 activations) is not correct by the same predicate."""

from chipbench.bench import load_cell
from chipbench.harness import run_cell
from chipbench.tests.tiny import TINY_LIMIT, make_bench, on_cpu


def test_mix_added_as_a_file_runs_and_the_control_fails(tmp_path,
                                                        monkeypatch):
    import time

    on_cpu(monkeypatch)
    cell = load_cell(make_bench(tmp_path, mix_name="onlyafile"),
                     root=tmp_path, bench_dir=tmp_path / "chipbench")
    res = run_cell(cell, seed=2**31 + 99, seconds=2.0, trace=False,
                   t0=time.perf_counter(), controls=("int4",))
    assert res["correct"], res["checks"]
    assert res["controls"]["int4"] is False
    assert set(res["metrics"]) == {"out_tok_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    checks = res["checks"]
    assert checks["logit_gap"]["value"] <= TINY_LIMIT
    assert checks["control_int4_logit_gap"]["value"] > TINY_LIMIT
    assert checks["tokens_checked"]["value"] >= 8
