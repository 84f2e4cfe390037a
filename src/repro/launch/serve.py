"""Serving driver: batched requests through the slot or paged engine.

CPU-smoke examples:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-110b --smoke \
      --requests 6 --max-new 16 --quant int4_packed --temperature 0.8
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-110b --smoke \
      --engine continuous --page-size 8 --stream --requests 6
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..models import transformer as T
from ..models.registry import get_config
from ..runtime.compile_cache import enable_compile_cache
from ..serving import (
    ContinuousEngine,
    Engine,
    ReplicaFront,
    SamplingParams,
    ServeConfig,
)


def init_serving_params(cfg, seed: int = 0):
    """Random weights for ``cfg`` in its compute dtype (``cfg.dtype``),
    made on the default device under jit — a full-width model never passes
    through a host or float32 copy."""
    return jax.jit(lambda key: T.init_params(key, cfg, jnp.dtype(cfg.dtype)))(
        jax.random.PRNGKey(seed)
    )


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--engine", default="slot",
                    choices=["slot", "continuous"],
                    help="'slot' = fixed-slot FIFO over dense per-slot cache "
                         "windows; 'continuous' = continuous batching over "
                         "a paged KV cache (attention-only families)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="continuous: KV tokens per physical cache page")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="continuous: physical page pool size (default: "
                         "memory parity with the slot engine's windows)")
    ap.add_argument("--watermark-pages", type=int, default=None,
                    help="continuous: free-page floor admission keeps "
                         "(default: one growth page per lane)")
    ap.add_argument("--stream", action="store_true",
                    help="print (rid, token) pairs as they are emitted "
                         "instead of waiting for requests to finish")
    ap.add_argument("--quant", default="native",
                    choices=["native", "int8", "int4_packed", "dsp_packed",
                             "dsp_tuned", "dsp_mixed"])
    ap.add_argument("--use-kernel", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="packed modes: run the compiled Pallas kernels "
                         "(--use-kernel) or the jnp references "
                         "(--no-use-kernel); default: kernels on a TPU at "
                         "--tp 1, references elsewhere")
    ap.add_argument("--error-budget", type=float, default=0.5,
                    help="dsp_tuned: max MAE per extraction a plan may incur")
    def _plan_bits(arg: str) -> tuple[int, int] | str:
        if arg == "auto":
            return "auto"
        try:
            a_bits, w_bits = (int(b) for b in arg.split(","))
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"--plan-bits wants two comma-separated ints 'A,W' "
                f"(e.g. 8,8) or 'auto', got {arg!r}"
            )
        return a_bits, w_bits

    ap.add_argument("--plan-bits", type=_plan_bits, default=(4, 4),
                    metavar="A,W|auto",
                    help="dsp_tuned: operand widths to plan for, e.g. 8,8 "
                         "(8-bit widths serve multi-DSP column-packed "
                         "plans); 'auto' allocates widths per layer by "
                         "measured sensitivity (= --quant dsp_mixed)")
    ap.add_argument("--mixed-budget", type=float, default=0.05,
                    help="dsp_mixed: model-level error budget (total added "
                         "logit-KL on the calibration forward) the greedy "
                         "per-layer width allocator may spend; 0 serves the "
                         "uniform widest-candidate plan")
    ap.add_argument("--calib-tokens", type=int, default=32,
                    help="dsp_mixed: calibration tokens per sequence for "
                         "the sensitivity pass (seeded from --seed)")
    ap.add_argument("--autotune-plans", action="store_true",
                    help="dsp_tuned: wall-clock block-size sweep per layer "
                         "shape and per serving phase (slower engine build, "
                         "measured ranking; decode GEMVs get their own "
                         "small-M blocks)")
    ap.add_argument("--no-prepack", dest="prepack", action="store_false",
                    help="skip building device-resident prepacked weight "
                         "operands at engine build (storage-only leaves; "
                         "decode falls back to per-step packing)")
    ap.add_argument("--fuse", dest="fuse_projections", default="none",
                    choices=["none", "mlp", "all"],
                    help="engine-build projection fusion for packed modes: "
                         "'mlp' fuses up|gate, 'all' also fuses q|k|v "
                         "(fused splits cost more than they save inside the "
                         "scanned CPU decode step — default 'none'; flip on "
                         "for TPU)")
    ap.add_argument("--plan-db", default=None, metavar="DIR",
                    help="persisted plan database directory "
                         "(tuning.plandb): engine build consults it before "
                         "running the dsp_tuned/dsp_mixed plan searches and "
                         "stores cold results back — a restarted engine "
                         "builds in seconds")
    ap.add_argument("--governor", action="store_true",
                    help="load-adaptive precision governor "
                         "(serving.governor): hold a uniformly-narrow "
                         "fallback weight tier beside the primary plan and "
                         "swap to it when the queue backs up — graceful "
                         "quality degradation instead of latency collapse "
                         "(dsp_tuned/dsp_mixed only)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request wall-clock deadline from submission; "
                         "requests past it are shed (finish_reason "
                         "'deadline') instead of occupying lanes")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard packed weights over "
                         "--tp devices (runtime.tp_packed; decode "
                         "stays bit-identical to --tp 1). CPU smoke: set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind a "
                         "join-shortest-queue front (serving.replica); each "
                         "replica is a full engine on its own --tp devices "
                         "— combine with --tp for 2D scaling")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_serving_params(cfg)
    engine_cls = ContinuousEngine if args.engine == "continuous" else Engine
    serve_cfg = ServeConfig(
        n_slots=args.slots, max_len=args.max_len,
        prefill_chunk=args.prefill_chunk, quant_mode=args.quant,
        use_kernel=args.use_kernel, seed=args.seed,
        error_budget=args.error_budget,
        autotune_plans=args.autotune_plans,
        plan_bits="auto" if args.quant == "dsp_mixed" else args.plan_bits,
        mixed_budget=args.mixed_budget,
        calib_tokens=args.calib_tokens,
        prepack=args.prepack,
        fuse_projections=args.fuse_projections,
        page_size=args.page_size,
        n_pages=args.n_pages,
        watermark_pages=args.watermark_pages,
        plan_db=args.plan_db,
        governor=args.governor,
        deadline_ms=args.deadline_ms,
        tp=args.tp,
    )
    if args.replicas > 1:
        if args.stream:
            raise SystemExit("--replicas does not support --stream "
                             "(per-replica token streams interleave)")
        front = ReplicaFront(cfg, params, serve_cfg,
                             n_replicas=args.replicas,
                             engine_cls=engine_cls)
        rng = np.random.default_rng(0)
        prompts = [
            list(rng.integers(2, cfg.vocab_size, size=rng.integers(4, 10)))
            for _ in range(args.requests)
        ]
        t0 = time.time()
        outputs = front.generate(prompts, max_new=args.max_new)
        dt = time.time() - t0
        total_tokens = sum(len(v) for v in outputs.values())
        for grid, toks in sorted(outputs.items()):
            print(f"[serve] request {grid} (replica "
                  f"{front.replica_of(grid)}): {len(toks)} tokens "
                  f"-> {toks[:8]}...")
        stats = front.stats()
        print(f"[serve] {total_tokens} tokens in {dt:.2f}s across "
              f"{stats['n_replicas']} replicas (tp={args.tp}, "
              f"quant={args.quant}, finished {stats['finished']})")
        return
    engine = engine_cls(cfg, params, serve_cfg)
    if engine.mixed_allocation is not None:
        alloc = engine.mixed_allocation
        print(f"[serve] mixed-precision allocation (budget "
              f"{alloc.budget:.4g}, predicted error "
              f"{alloc.predicted_error:.4g}, cost "
              f"{alloc.cost_vs_uniform_base:.2f}x uniform "
              f"a{alloc.base_bits[0]}w{alloc.base_bits[1]}):")
        for path, (a, w) in sorted(alloc.assignments.items()):
            print(f"[serve]   {path}: a{a}w{w} "
                  f"({alloc.plans[path].name})")
    if engine.plan_table:
        plans = {r.name for r in engine.plan_table.values()}
        print(f"[serve] tuned packing plans (budget {args.error_budget}): "
              + ", ".join(sorted(plans)))
        if args.autotune_plans:
            per_phase = {
                f"{r.name}: prefill {r.block} / decode {r.decode_block}"
                for r in engine.plan_table.values()
            }
            print("[serve] per-phase tuned blocks: "
                  + "; ".join(sorted(per_phase)))
    if engine.tiers is not None:
        print("[serve] governor tiers: " + "; ".join(
            f"{i}:{t.name} (certified MAE <= {t.max_certified_mae:g})"
            for i, t in enumerate(engine.tiers)))
    sampling = SamplingParams(args.temperature, args.top_k, args.top_p)

    rng = np.random.default_rng(0)
    prompts = [
        list(rng.integers(2, cfg.vocab_size, size=rng.integers(4, 10)))
        for _ in range(args.requests)
    ]
    t0 = time.time()
    if args.stream:
        rids = [engine.submit(p, max_new=args.max_new, sampling=sampling,
                              admit=False) for p in prompts]
        while engine.active.any() or engine.scheduler.n_queued:
            engine.step()
            for rid, tok in engine.drain_stream():
                print(f"[stream] rid {rid} -> {tok}")
        outputs = {r: list(engine.scheduler.requests[r].tokens) for r in rids}
    else:
        outputs = engine.generate(prompts, max_new=args.max_new,
                                  sampling=sampling)
    dt = time.time() - t0
    total_tokens = sum(len(v) for v in outputs.values())
    for rid, toks in sorted(outputs.items()):
        reason = engine.scheduler.requests[rid].finish_reason
        print(f"[serve] request {rid}: {len(toks)} tokens ({reason}) "
              f"-> {toks[:8]}...")
    stats = engine.stats()
    rates = (f"prefill {stats['prefill_tok_s']:.1f} tok/s, "
             f"decode {stats['decode_tok_s']:.1f} tok/s")
    if args.engine == "continuous":
        # a continuous step runs both phases: each rate is over the time
        # of the steps that carried its phase
        rates = (f"{rates} over the steps carrying each, median step "
                 f"{stats['median_step_s'] * 1e3:.0f}ms")
    # the share of the rows the batched programs computed that were real
    rows = ", ".join(
        f"{phase} {100 * stats[f'{phase}_tokens'] / stats[f'{phase}_rows']:.0f}%"
        for phase in ("prefill", "decode") if stats[f"{phase}_rows"])
    print(f"[serve] {total_tokens} tokens in {dt:.2f}s "
          f"(engine={args.engine}, quant={engine.scfg.quant_mode}, "
          f"{rates}, "
          f"ttft p50 {stats['p50_ttft_s'] * 1e3:.0f}ms / "
          f"p99 {stats['p99_ttft_s'] * 1e3:.0f}ms, "
          f"mean latency {stats['mean_latency_s'] * 1e3:.0f}ms)")
    if rows:
        print(f"[serve] real rows: {rows}")
    if args.engine == "continuous":
        print(f"[serve] pages: {stats['n_pages'] - stats['free_pages']}"
              f"/{stats['n_pages']} in use at exit "
              f"(page_size {stats['page_size']}, watermark "
              f"{stats['watermark_pages']}, "
              f"preempted {stats['preempted']})")
    if args.deadline_ms is not None:
        print(f"[serve] shed {stats['shed']} of "
              f"{stats['finished'] + stats['cancelled']} requests at the "
              f"{args.deadline_ms:.0f}ms deadline")
    if "plan_db" in stats:
        db = stats["plan_db"]
        warm = "warm" if db["hits"] else "cold"
        print(f"[serve] plan db {db['directory']}: {warm} build "
              f"({db['hits']} hit / {db['misses']} miss / "
              f"{db['stale']} stale, key {db['key'][:12]})")
    if "governor" in stats:
        g = stats["governor"]
        print(f"[serve] governor: tier {g['tier']} ({g['tier_name']}) "
              f"after {g['swaps']} swaps over {g['observations']} steps")


if __name__ == "__main__":
    main()
