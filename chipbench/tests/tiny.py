"""A benchmark tree at a size a CPU test can run: one tiny configuration
of each engine, one tiny closed-loop mix, the dense family module,
written as files only; and ``on_cpu``, which lets a test drive a run on
the CPU."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

from chipbench.bench import HERE

MODEL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
             vocab_size=256, sliding_window=32, rope_theta=10000.0,
             norm_eps=1e-5, qkv_bias=False, mlp_variant="swiglu")
# widest logit gap of the tiny model on the CPU: 0-0.04 for the program,
# 0.29-0.46 for the int4-activation control (two seeds per engine)
TINY_LIMIT = 0.15


def config(engine: str = "slot") -> dict:
    model = dict(MODEL)
    if engine == "continuous":
        model.update(sliding_window=None, mlp_variant="gelu", qkv_bias=True)
    return {
        "name": f"tiny-{engine}",
        "source": "a CPU-sized cut of the registry's models",
        "arch": "h2o-danube-3-4b" if engine == "slot" else "starcoder2-7b",
        "family": "dense", "model": model, "engine": engine,
        "serve": {"n_slots": 4, "max_len": 64, "prefill_chunk": 4,
                  "quant_mode": "int4_packed", "eos_token": 256},
        "check": {"logit_gap": TINY_LIMIT},
    }


MIX = {
    "kind": "closed_loop", "clients": 4,
    "prompt": {"median": 8, "sigma": 0.5, "min": 2, "max": 20},
    "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 24},
    "pool": 4, "warmup_steps": 4,
    "check": {"requests": 4, "min_tokens": 8},
}


def make_bench(root: Path, engine: str = "slot", mix_name: str = "tinymix",
               metric: str | None = None) -> str:
    """Write ``root/BENCHMARK.json`` and ``root/chipbench/{configs,traffic,
    metrics,families}`` with one cell; returns the cell's name."""
    bench = root / "chipbench"
    for sub in ("configs", "traffic", "metrics", "families"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    shutil.copy(HERE / "families" / "dense.py", bench / "families")
    conf = config(engine)
    (bench / "configs" / f"{conf['name']}.json").write_text(json.dumps(conf))
    (bench / "traffic" / f"{mix_name}.json").write_text(json.dumps(MIX))
    per_layer = []
    if metric is not None:
        shutil.copy(HERE / "metrics" / "decode_step_ms.py",
                    bench / "metrics" / f"{metric}.py")
        per_layer.append({"name": metric, "unit": "ms", "better": "lower",
                          "source": "device_trace", "layer": "step",
                          "moves": "out_tok_s"})
    cell = f"{conf['name']}.{mix_name}"
    spec = {
        "command": ["python3", "-m", "chipbench.run"],
        "paths": ["chipbench"], "run_seconds": 2,
        "configs": [{"name": conf["name"], "source": conf["source"],
                     "file": f"chipbench/configs/{conf['name']}.json",
                     "reduced": [], "why": "CPU test"}],
        "workloads": [{"name": cell, "config": conf["name"],
                       "traffic": mix_name, "chips": 1, "why": "CPU test"}],
        "end_to_end": [
            {"name": n, "unit": u, "better": b, "bound": 0.25,
             "source": "host_clock"}
            for n, u, b in [("out_tok_s", "tokens/s", "higher"),
                            ("itl_p95_ms", "ms", "lower"),
                            ("setup_s", "s", "lower")]],
        "per_layer": per_layer,
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


def on_cpu(monkeypatch) -> None:
    """Skip the harness's look for a chip and its compile cache: the run
    takes the CPU's devices and the v5e peak table."""
    import jax

    from chipbench import harness, roofline

    table = roofline.peaks("TPU v5 lite")
    monkeypatch.setattr(harness, "devices_for",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    monkeypatch.setattr(roofline, "peaks", lambda kind: table)
