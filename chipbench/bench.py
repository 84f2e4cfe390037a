"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` at the checkout's root names every cell; each cell
names a configuration (``configs`` entry, whose ``file`` is the JSON the
cell runs) and a traffic mix (``traffic/<traffic>.json`` beside this
file).  Each per-layer metric ``<name>`` is read by the module
``metrics/<name>.py``, which defines ``read(ctx) -> float | None``.  A
later cell, mix or metric is added as files and entries only.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    moves: str | None = None
    workloads: tuple[str, ...] | None = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict[str, Any]
    traffic: dict[str, Any]
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]
    readers: dict[str, Callable]


def _metric(entry: dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(name=entry["name"], unit=entry["unit"],
                  moves=entry.get("moves"),
                  workloads=None if wl is None else tuple(wl))


def load_reader(bench_dir: Path, name: str) -> Callable:
    """``read`` of ``metrics/<name>.py``, loaded from its path (a metric
    name may hold dots, which a module name may not)."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path | None = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with everything it
    needs loaded; raises ``KeyError`` / ``FileNotFoundError`` when a
    named piece is missing."""
    bench_dir = HERE if bench_dir is None else bench_dir
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic_path = bench_dir / "traffic" / f"{w['traffic']}.json"
    if not traffic_path.is_file():
        raise FileNotFoundError(f"no traffic mix {w['traffic']!r}: "
                                f"{traffic_path}")
    traffic = json.loads(traffic_path.read_text())

    def applies(m: Metric) -> bool:
        return m.workloads is None or name in m.workloads

    e2e = tuple(m for m in map(_metric, spec["end_to_end"]) if applies(m))
    reported = {m.name for m in e2e}
    per_layer = tuple(
        m for m in map(_metric, spec["per_layer"])
        if (name in m.workloads if m.workloads is not None
            else m.moves in reported)
    )
    readers = {m.name: load_reader(bench_dir, m.name) for m in per_layer}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                readers=readers)
