"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces the compiled SPMD executable on 512 (or 256)
placeholder host devices and records:
  * ``memory_analysis``  — per-device bytes (proves the sharding fits HBM)
  * ``cost_analysis``    — HLO FLOPs / bytes accessed (roofline numerator)
  * collective bytes     — parsed from the optimized HLO (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute)

Artifacts land in ``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` and are
consumed by ``benchmarks/roofline.py`` and EXPERIMENTS.md §Dry-run.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-110b \
      --shape train_4k [--multi-pod] [--all] [--out artifacts/dryrun]
"""

import os

os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512"
)))

# ^ MUST precede every other import (jax locks device count on first init).
# Appends to the caller's flags; nothing on the serving path imports this.

import argparse
import dataclasses
import json
import math
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import transformer as T
from ..models.registry import get_config, list_archs
from ..optim.adamw import AdamWConfig, adamw_init
from ..runtime.sharding import (
    cache_pspec,
    fsdp_axes,
    param_shardings,
)
from .mesh import make_production_mesh
from .shapes import SHAPES, cache_specs, input_specs, supported
from .steps import make_prefill_step, make_serve_step, make_train_step

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 0.5, "u4": 0.5, "pred": 1,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(shape_str: str) -> float:
    """'f32[256,8192]' -> bytes."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_str)
    if not m:
        return 0.0
    dt, dims = m.groups()
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum result bytes of every collective op in the optimized HLO."""
    out: dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    shape_re = re.compile(r"[a-z0-9]+\[[0-9,]*\]")
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = (.*?) ([a-z\-]+-start|[a-z\-]+)\(", stripped)
        if not m:
            continue
        shapes_part, opname = m.groups()
        opname = opname.removesuffix("-start")
        if opname not in _COLLECTIVES:
            continue
        total = sum(_shape_bytes(s) for s in shape_re.findall(shapes_part))
        out[opname] += total
    out["total"] = sum(out.values())
    return out


def _mem_dict(mem) -> dict:
    keys = (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    )
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v)
    return out


VARIANTS = (
    "baseline", "flash", "tp_serve", "int4_serve", "flash_rs", "mb4",
    "flash_mb4", "tp_fix", "tp_fix_flash",
)


def _apply_variant(cfg, variant: str):
    if variant in ("flash", "flash_rs", "tp_fix_flash"):
        return dataclasses.replace(cfg, attention_chunk=1024)
    if variant == "mb4":
        return dataclasses.replace(cfg, remat="full")
    if variant == "flash_mb4":
        return dataclasses.replace(cfg, attention_chunk=1024, remat="full")
    return cfg


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    smoke: bool = False,
    depth_groups: int | None = None,
    variant: str = "baseline",
):
    """Build the jitted step for one cell and lower it.

    ``depth_groups`` replaces the model with an UNROLLED ``k``-group-deep
    variant — used to derive exact per-group cost increments, because XLA's
    cost analysis counts a while-loop (scan) body once regardless of trip
    count.  ``None`` = the real full-depth scanned model.

    ``variant`` selects a §Perf optimization (see VARIANTS): ``flash`` =
    chunked online-softmax attention; ``tp_serve`` = TP-only serving params;
    ``int4_serve`` = packed int4 serving weights + TP-only.
    """
    cfg = _apply_variant(get_config(arch, smoke=smoke), variant)
    if depth_groups is not None:
        enc = (
            {"n_encoder_layers": depth_groups}
            if cfg.family == "encdec"
            else {}
        )
        cfg = dataclasses.replace(
            cfg,
            n_layers=depth_groups * cfg.group_size,
            scan_layers=False,
            **enc,
        )
    shape = SHAPES[shape_name]
    ok, reason = supported(cfg, shape)
    if not ok:
        raise ValueError(f"unsupported cell: {reason}")
    mesh = make_production_mesh(multi_pod=multi_pod)
    fsdp = fsdp_axes(mesh)
    fs = fsdp if len(fsdp) > 1 else fsdp[0]
    batch = input_specs(cfg, shape)
    repl = NamedSharding(mesh, P())

    def batch_shardings(tree):
        out = {}
        for k, v in tree.items():
            spec = [None] * len(v.shape)
            if v.shape[0] % (2 * 16 if multi_pod else 16) == 0:
                spec[0] = fs
            out[k] = NamedSharding(mesh, P(*spec))
        return out

    if shape.kind == "train":
        params_shape = jax.eval_shape(
            lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
        )
        opt_shape = jax.eval_shape(lambda: adamw_init(params_shape))
        p_shard = param_shardings(params_shape, mesh)
        state_shard = {
            "params": p_shard,
            "opt": {
                "m": p_shard,
                "v": p_shard,
                "step": repl,
            },
        }
        state_shape = {"params": params_shape, "opt": opt_shape}
        step_fn = make_train_step(
            cfg, AdamWConfig(), mesh,
            grad_shardings=p_shard if variant == "flash_rs" else None,
            microbatches=4 if variant in ("mb4", "flash_mb4") else 1,
        )
        in_shardings = (state_shard, batch_shardings(batch))
        jitted = jax.jit(
            step_fn,
            in_shardings=in_shardings,
            out_shardings=(state_shard, None),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(state_shape, batch)
        return lowered, cfg, mesh

    serving_tp_only = variant in ("tp_serve", "int4_serve")
    if variant == "int4_serve":
        from ..core.packed_params import quantize_params_for_serving

        params_shape = jax.eval_shape(
            lambda: quantize_params_for_serving(
                T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
            )
        )
    else:
        params_shape = jax.eval_shape(
            lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
        )
    p_shard = param_shardings(params_shape, mesh, serving=serving_tp_only)

    def sliced_group_shardings():
        if "groups" not in params_shape:
            return None
        sliced = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
            params_shape["groups"],
        )
        return param_shardings(sliced, mesh, serving=serving_tp_only)

    if shape.kind == "prefill":
        step_fn = make_prefill_step(cfg, mesh, sliced_group_shardings())
        jitted = jax.jit(
            step_fn, in_shardings=(p_shard, batch_shardings(batch))
        )
        lowered = jitted.lower(params_shape, batch)
        return lowered, cfg, mesh

    # decode
    cache_shape = cache_specs(cfg, shape)
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    c_shard = jax.tree.unflatten(
        treedef,
        [
            NamedSharding(
                mesh,
                cache_pspec(
                    mesh, leaf.shape, shape.global_batch,
                    path="/".join(
                        str(getattr(q, "key", getattr(q, "idx", q))) for q in pth
                    ),
                ),
            )
            for pth, leaf in flat
        ],
    )
    step_fn = make_serve_step(cfg, mesh, sliced_group_shardings())
    jitted = jax.jit(
        step_fn,
        in_shardings=(p_shard, c_shard, batch_shardings(input_specs(cfg, shape))),
        out_shardings=(None, c_shard),
        donate_argnums=(1,),
    )
    lowered = jitted.lower(params_shape, cache_shape, input_specs(cfg, shape))
    return lowered, cfg, mesh


def _packing_plan_verdicts(cfg) -> dict:
    """Per-layer serving plan + certificate verdict, from shapes alone.

    ``plan_linear_layers`` only reads leaf shapes, so the abstract
    ``eval_shape`` tree is enough — no weights are materialized at dry-run
    scale.  Each row carries the selected plan name and its certificate's
    exact/bounded verdict (plus the certified per-extraction WCE when
    bounded), mirroring what ``quantize_for_serving`` would build."""
    from ..tuning.tuner import plan_linear_layers

    params_shape = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    out = {}
    for lpath, report in plan_linear_layers(params_shape).items():
        cert = report.certificate
        out[lpath] = {
            "plan": report.name,
            "verdict": cert.verdict,
            "wce_per_extraction": cert.wce_per_extraction,
        }
    return out


def run_cell(
    arch: str, shape_name: str, multi_pod: bool, out_dir: str,
    smoke: bool = False, variant: str = "baseline",
) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}"
    if variant != "baseline":
        tag += f"__{variant}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{tag}.json")
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    t0 = time.time()
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "ok": False,
        "variant": variant,
    }
    try:
        # 1) full-depth scanned model: proves sharding/memory at 256/512 dev
        lowered, cfg, mesh = lower_cell(arch, shape_name, multi_pod, smoke,
                                        variant=variant)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        def _cost(compiled_exe):
            cost = compiled_exe.cost_analysis() or {}
            if isinstance(cost, (list, tuple)):
                # older jaxlib returns a one-element list of dicts
                cost = cost[0] if cost else {}
            coll = collective_bytes(compiled_exe.as_text())
            return {
                "flops": float(cost.get("flops", 0.0)),
                "bytes": float(cost.get("bytes accessed", 0.0)),
                "coll": coll,
            }

        # 2) unrolled 1-group / 2-group variants: exact per-group increments
        #    (XLA cost analysis counts a scan body once, so the full-depth
        #    numbers must be reconstructed as f1 + (G-1)·(f2-f1)).
        c1 = _cost(lower_cell(arch, shape_name, multi_pod, smoke, 1,
                              variant=variant)[0].compile())
        c2 = _cost(lower_cell(arch, shape_name, multi_pod, smoke, 2,
                              variant=variant)[0].compile())
        groups = cfg.n_groups

        def extrap(key):
            return c1[key] + (groups - 1) * (c2[key] - c1[key])

        coll = {
            k: c1["coll"][k] + (groups - 1) * (c2["coll"][k] - c1["coll"][k])
            for k in c1["coll"]
        }
        mem = _mem_dict(compiled.memory_analysis())
        n_params = sum(
            math.prod(leaf.shape)
            for leaf in jax.tree.leaves(
                jax.eval_shape(
                    lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
                )
            )
        )
        record.update(
            ok=True,
            flops=extrap("flops"),
            bytes_accessed=extrap("bytes"),
            collectives=coll,
            scan_body={"flops_1g": c1["flops"], "flops_2g": c2["flops"]},
            memory=mem,
            n_devices=int(mesh.devices.size),
            n_params=int(n_params),
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
        )
        print(f"[dryrun] {tag}: OK flops/dev={record['flops']:.3e} "
              f"coll={coll['total']:.3e}B lower={t_lower:.0f}s compile={t_compile:.0f}s",
              flush=True)
        if variant == "int4_serve":
            # which plan each layer would serve, with its static error
            # pedigree — the registry-config projection of exact vs
            # bounded serving arithmetic (non-fatal: a planning failure
            # must not mask a successful lowering)
            try:
                record["packing_plans"] = _packing_plan_verdicts(cfg)
                for lpath, row in sorted(record["packing_plans"].items()):
                    extra = (
                        "" if row["verdict"] == "exact" else
                        f" wce/extraction={row['wce_per_extraction']}"
                    )
                    print(f"[dryrun]   {lpath}: {row['plan']} "
                          f"[{row['verdict']}{extra}]", flush=True)
            except Exception as e:  # noqa: BLE001
                record["packing_plans_error"] = f"{type(e).__name__}: {e}"
                print(f"[dryrun]   packing plans unavailable: {e}",
                      flush=True)
    except Exception as e:  # noqa: BLE001 — record the failure for the report
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
        print(f"[dryrun] {tag}: FAIL {record['error'][:200]}", flush=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


PACKED_TP_ARCHS = ("qwen1.5-110b", "dbrx-132b", "jamba-v0.1-52b")


def packed_tp_projection(arch: str, tp: int, smoke: bool = False) -> dict:
    """Per-shard packed-serving memory + interconnect projection, from
    shapes alone.

    Projects what ``runtime.tp_packed.shard_params_tp`` would place on
    each of ``tp`` devices — prepacked int32 weight words plus per-column
    scales — and what one decode step moves over the interconnect, without
    constructing a single weight: the tree is ``jax.eval_shape`` abstract
    and every number below is arithmetic on leaf shapes.

    Accounting (per (…, K, N) packable leaf, ``lead`` = stacked dims):

    * words HBM: ``lead · K/2 · N · 4`` bytes (two int4 pairs per int32
      word, the prepacked operand layout) — divided by ``tp`` along N for
      column-parallel leaves and along K for row-parallel ones, when the
      axis divides; otherwise the leaf replicates.
    * scales: ``lead · N · 4`` bytes, replicated (per-output-channel).
    * decode interconnect, batch row ``m=1``: column-parallel leaves
      all-gather their output row (ring: ``(tp-1)/tp · N·4`` bytes);
      row-parallel leaves all-reduce the accumulator row (ring:
      ``2·(tp-1)/tp · N·4`` bytes).  Replicated leaves move nothing.
    """
    from ..core.packed_params import iter_packable_weights
    from ..runtime.sharding import linear_partition

    cfg = get_config(arch, smoke=smoke)
    params_shape = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    )
    rows = {}
    tot_shard = tot_repl = tot_net = 0.0
    for path, leaf in iter_packable_weights(params_shape):
        *lead, k_dim, n_dim = leaf.shape
        lead_n = math.prod(lead) if lead else 1
        words = lead_n * (k_dim // 2) * n_dim * 4.0
        scales = lead_n * n_dim * 4.0
        kind = linear_partition(path)
        if kind == "col" and n_dim % tp == 0:
            shard, net = words / tp, (tp - 1) / tp * n_dim * 4.0
        elif kind == "row" and k_dim % tp == 0:
            shard, net = words / tp, 2 * (tp - 1) / tp * n_dim * 4.0
        else:
            kind, shard, net = "replicate", words, 0.0
        rows[path] = {
            "shape": list(leaf.shape), "partition": kind,
            "words_bytes_per_shard": shard, "scale_bytes": scales,
            "decode_net_bytes": lead_n * net,
        }
        tot_shard += shard
        tot_repl += scales
        tot_net += lead_n * net
    return {
        "arch": arch, "tp": tp,
        "packed_words_bytes_per_shard": tot_shard,
        "replicated_scale_bytes": tot_repl,
        "decode_step_interconnect_bytes": tot_net,
        "layers": rows,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="every supported cell")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    ap.add_argument("--packed-tp", type=int, default=None, metavar="N",
                    help="project per-shard packed-weight HBM and per-"
                         "decode-step interconnect bytes for an N-way "
                         "tensor-parallel packed engine (shapes only, no "
                         "weights; default archs: "
                         + ", ".join(PACKED_TP_ARCHS) + ")")
    args = ap.parse_args()

    if args.packed_tp is not None:
        archs = [args.arch] if args.arch else list(PACKED_TP_ARCHS)
        for arch in archs:
            rec = packed_tp_projection(arch, args.packed_tp, args.smoke)
            gib = 1 << 30
            print(f"[dryrun] {arch} packed tp={args.packed_tp}: "
                  f"{rec['packed_words_bytes_per_shard'] / gib:.2f} GiB "
                  f"packed words/shard + "
                  f"{rec['replicated_scale_bytes'] / gib:.3f} GiB "
                  f"replicated scales, "
                  f"{rec['decode_step_interconnect_bytes'] / 1e6:.2f} MB "
                  f"interconnect per decode row", flush=True)
            os.makedirs(args.out, exist_ok=True)
            path = os.path.join(
                args.out, f"{arch}__packed_tp{args.packed_tp}.json"
            )
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
        return

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if args.all:
        n_ok = n_skip = n_fail = 0
        for arch in list_archs():
            cfg = get_config(arch)
            for shape_name, shape in SHAPES.items():
                ok, reason = supported(cfg, shape)
                if not ok:
                    n_skip += 1
                    print(f"[dryrun] {arch}__{shape_name}: SKIP ({reason})", flush=True)
                    continue
                for mp in meshes:
                    rec = run_cell(arch, shape_name, mp, args.out, args.smoke,
                                   args.variant)
                    n_ok += rec["ok"]
                    n_fail += not rec["ok"]
        print(f"[dryrun] done: ok={n_ok} fail={n_fail} skipped-cells={n_skip}")
        return

    for mp in meshes:
        rec = run_cell(args.arch, args.shape, mp, args.out, args.smoke,
                       args.variant)
        if rec["ok"]:
            print(json.dumps({k: v for k, v in rec.items() if k != "traceback"}, indent=1))


if __name__ == "__main__":
    main()
