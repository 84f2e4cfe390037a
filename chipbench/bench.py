"""Find a cell's configuration, model, traffic mix and metric readers by
name.

``BENCHMARK.json`` at the checkout's root names every cell; each cell
names a configuration (``configs`` entry, whose ``file`` is the JSON the
cell runs) and a traffic mix (``traffic/<traffic>.json`` beside this
file).  The configuration's required key ``family`` names the module
``families/<family>.py`` that holds its model (``FAMILY_API``):

- ``Model.from_config(model)``: the hashable model of the configuration's
  ``model`` dict, with at least ``n_layers``, ``vocab_size`` and
  ``norm_eps`` (the reference's loop and final norm read them);
- ``params(m, seed_key)``: the whole bf16 tree in the program's layout,
  made on the device in one jitted call;
- ``embed_and_head(m, seed_key)``: the embedding and the LM head of that
  tree;
- ``layer_weights(m, seed_key, i)``: layer ``i``'s weights, drawn again
  exactly as ``params`` drew them;
- ``layer(m, x, w, bits)``: one reference layer over x (B, S, d) in
  float32 at ``HIGHEST``, its weights ``w`` to int4 and ``bits``-bit
  activations into every linear (S is a multiple of
  ``reference.Q_BLOCK``);
- ``position_flops(model, pos)``, ``prompt_flops(model, length)``,
  ``lm_head_flops(model)``: model FLOPs of one decoded position, of a
  prompt's prefill and of one LM head row;
- ``forward_min_time_s(model, rows, peak, lm_head)``: the least time of
  the packed linears of one forward over ``rows`` rows.

Each per-layer metric ``<name>`` is read by the module
``metrics/<name>.py``, which defines ``read(ctx) -> float | None``.  A
later configuration, model, mix or metric is added as files and entries
only.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Callable

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
FAMILY_API = ("Model", "params", "embed_and_head", "layer_weights", "layer",
              "position_flops", "prompt_flops", "lm_head_flops",
              "forward_min_time_s")


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
    family: ModuleType
    traffic: dict[str, Any]
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]
    readers: dict[str, Callable]


def _metric(entry: dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(name=entry["name"], unit=entry["unit"],
                  moves=entry.get("moves"),
                  workloads=None if wl is None else tuple(wl))


def _load(path: Path, kind: str, name: str) -> ModuleType:
    """The module at ``path``, loaded from its path (a metric name may
    hold dots, which a module name may not)."""
    if not path.is_file():
        raise FileNotFoundError(f"no module for {kind} {name!r}: {path}")
    mod_name = f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs, as an import would: a dataclass looks its
    # module up while it is made
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: Path, name: str) -> Callable:
    """``read`` of ``metrics/<name>.py``."""
    return _load(bench_dir / "metrics" / f"{name}.py", "metric", name).read


def load_family(bench_dir: Path, name: str) -> ModuleType:
    """The module ``families/<name>.py``; raises ``FileNotFoundError`` when
    it is missing and ``AttributeError`` when it lacks a name of
    ``FAMILY_API``."""
    path = bench_dir / "families" / f"{name}.py"
    mod = _load(path, "family", name)
    missing = [n for n in FAMILY_API if not hasattr(mod, n)]
    if missing:
        raise AttributeError(f"{path} defines no {', '.join(missing)}")
    return mod


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
    config_path = root / configs[w["config"]]["file"]
    config = json.loads(config_path.read_text())
    if "family" not in config:
        raise KeyError(f"{config_path} has no key 'family' (the module "
                       "families/<family>.py that holds its model)")
    family = load_family(bench_dir, config["family"])
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
                family=family, traffic=traffic, end_to_end=e2e,
                per_layer=per_layer, readers=readers)
