"""A configuration, a traffic mix and a per-layer metric added as files
only are found by name from BENCHMARK.json."""

import json
import re

import pytest

from chipbench.bench import ROOT, load_cell
from chipbench.tests.tiny import make_bench


def test_files_only_are_found_by_name(tmp_path):
    cell = make_bench(tmp_path, mix_name="newmix", metric="new_metric")
    got = load_cell(cell, root=tmp_path, bench_dir=tmp_path / "chipbench")
    assert got.traffic["clients"] == 4
    assert got.config["engine"] == "slot"
    assert got.family.__file__ == str(tmp_path / "chipbench" / "families"
                                      / "dense.py")
    assert [m.name for m in got.per_layer] == ["new_metric"]
    ctx = type("Ctx", (), {"trace": {
        "module_runs": {"jit__decode_step": 10},
        "module_s": {"jit__decode_step": 0.5}}})
    assert got.readers["new_metric"](ctx) == pytest.approx(50.0)
    assert {m.name for m in got.end_to_end} == {
        "out_tok_s", "itl_p95_ms", "setup_s"}


def _remove(bench, piece):
    """Take ``piece`` out of the tree ``make_bench`` wrote; returns the
    cell to load, the error and a name the error's message holds."""
    cell = "tiny-slot.gone"
    if piece == "traffic":
        (bench / "traffic" / "gone.json").unlink()
        return cell, FileNotFoundError, "gone.json"
    if piece == "workload":
        return "no.such.cell", KeyError, "no.such.cell"
    if piece == "family_key":
        path = bench / "configs" / "tiny-slot.json"
        conf = json.loads(path.read_text())
        del conf["family"]
        path.write_text(json.dumps(conf))
        return cell, KeyError, "tiny-slot.json"
    if piece == "family_module":
        (bench / "families" / "dense.py").unlink()
        return cell, FileNotFoundError, "dense.py"
    (bench / "families" / "dense.py").write_text("Model = None\n")
    return cell, AttributeError, "dense.py defines no params"


@pytest.mark.parametrize("piece", ["traffic", "workload", "family_key",
                                   "family_module", "family_api"])
def test_missing_pieces_are_errors(tmp_path, piece):
    make_bench(tmp_path, mix_name="gone", metric="absent")
    bench = tmp_path / "chipbench"
    cell, error, named = _remove(bench, piece)
    with pytest.raises(error, match=re.escape(named)):
        load_cell(cell, root=tmp_path, bench_dir=bench)


def test_the_repo_benchmark_loads_every_cell():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = load_cell(w["name"])
        assert {m.name for m in cell.per_layer} <= set(cell.readers)
        assert "setup_s" in {m.name for m in cell.end_to_end}
        assert cell.config["name"] == w["config"]
