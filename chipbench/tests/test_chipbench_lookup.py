"""A configuration, a traffic mix and a per-layer metric added as files
only are found by name from BENCHMARK.json."""

import json

import pytest

from chipbench.bench import ROOT, load_cell
from chipbench.tests.tiny import make_bench


def test_files_only_are_found_by_name(tmp_path):
    cell = make_bench(tmp_path, mix_name="newmix", metric="new_metric")
    got = load_cell(cell, root=tmp_path, bench_dir=tmp_path / "chipbench")
    assert got.traffic["clients"] == 4
    assert got.config["engine"] == "slot"
    assert [m.name for m in got.per_layer] == ["new_metric"]
    ctx = type("Ctx", (), {"trace": {
        "module_runs": {"jit__decode_step": 10},
        "module_s": {"jit__decode_step": 0.5}}})
    assert got.readers["new_metric"](ctx) == pytest.approx(50.0)
    assert {m.name for m in got.end_to_end} == {
        "out_tok_s", "itl_p95_ms", "setup_s"}


def test_missing_pieces_are_errors(tmp_path):
    cell = make_bench(tmp_path, mix_name="gone", metric="absent")
    (tmp_path / "chipbench" / "traffic" / "gone.json").unlink()
    with pytest.raises(FileNotFoundError):
        load_cell(cell, root=tmp_path, bench_dir=tmp_path / "chipbench")
    with pytest.raises(KeyError):
        load_cell("no.such.cell", root=tmp_path,
                  bench_dir=tmp_path / "chipbench")


def test_the_repo_benchmark_loads_every_cell():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        assert {m.name for m in cell.per_layer} <= set(cell.readers)
        assert "setup_s" in {m.name for m in cell.end_to_end}
        assert cell.config["name"] == w["config"]
