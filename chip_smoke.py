"""Bring-up smoke run of the packed serving path on a TPU.

Serves h2o-danube-3-4b at its published width and depth (24 layers,
d_model 3840, 32 heads with 8 KV heads, d_ff 10240, vocab 32000; random
weights from ``--seed``) through the entry points a user calls:
``get_config``, ``init_serving_params`` (``T.init_params`` under jit, in the
model's dtype), ``ServeConfig``, ``Engine`` and ``Engine.generate``.

One chip (the default) serves a few requests in each of three quant modes,
freeing each engine before the next is built:

* ``native`` — the first decode step's logits must match an uncached
  float32 full-context forward within ``NATIVE_RTOL``;
* ``int4_packed`` and ``dsp_tuned`` (plan widths a4w4) — the decode step
  must call compiled Pallas kernels (``tpu_custom_call``), and each kernel,
  compiled with ``interpret=False`` at a real layer of the served weights,
  must equal its jnp reference bit for bit.

Four chips (``--chips 4``) run only what exists across chips:
``int4_packed`` at ``tp=4`` must emit the ``tp=1`` tokens, with
first-decode logits within ``TP_RTOL`` of the ``tp=1`` engine's, and four
replicas behind ``ReplicaFront`` must each sit on a chip of their own and
match the ``tp=1`` engine the same way.

    python chip_smoke.py
    python chip_smoke.py --chips 4

Prints one line per measurement and, last, one JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Exits nonzero
and prints no result when JAX finds no TPU, when the repository's sources
are not next to this file, or when any phase fails.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "h2o-danube-3-4b"
# dsp_tuned holds the bf16 weights, the plan's int32 pair words (2 B per
# value) and, for the mr plans the chip's ranking selects, the int32
# contamination operands (4 B per value) while the engine builds: 24 layers
# need ~33 GB, so that phase alone serves the first DSP_LAYERS layers
DSP_LAYERS = 8
# native: relative L2 distance of the first decode step's logits (bf16
# engine) from an uncached float32 full-context forward, per request
NATIVE_RTOL = 5e-2
# --chips 4: relative L2 distance of a tp=4 engine's, or a replica's,
# first-decode logits from the tp=1 engine's, per request.  The packed
# integer arithmetic is exact on every path; the float ops around it may
# reduce in another order (on a 4-device host mesh, a 2-layer, d_model 256
# cut of this model left three requests bit-identical and moved one by
# 0.017).  A shard lost or counted twice in every layer moves the logits
# by a large fraction of their norm.  Half of NATIVE_RTOL
TP_RTOL = 2.5e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _prompts(cfg, n: int, length: int, seed: int) -> list[list[int]]:
    """Equal-length prompts, so one batched forward is their reference."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(2, cfg.vocab_size, size=length)))
            for _ in range(n)]


def _free(*objs) -> None:
    """Drop engines and weights: the jit caches key on the engine, so they
    are cleared too, or its device buffers would stay alive."""
    import jax

    del objs
    gc.collect()
    jax.clear_caches()
    gc.collect()


def _leaf(params, name: str):
    """The stacked MLP ``name`` weight leaf of a served tree."""
    return params["groups"]["mlp"][name]["w"]


def _memory(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_in_use={stats.get('bytes_in_use')}")


def _custom_calls(engine) -> int:
    """``tpu_custom_call``s (Pallas kernels) in the lowered decode step."""
    lowered = type(engine)._decode_step.lower(
        engine, engine.params, engine.cache, engine._dev_state
    )
    return lowered.as_text().count("tpu_custom_call")


def first_decode_logits(engine, prompts):
    """Prefill ``prompts``, sample each first token, and return the next
    (first decode) step's logits per request, (n, V) float32, with the
    prompts extended by their first tokens.  The requests are cancelled."""
    import numpy as np

    rids = [engine.submit(p, max_new=2) for p in prompts]  # prefill + 1st
    logits = engine.peek_logits()  # (n_slots, V), the next decode step's
    slot_of = {int(r): s for s, r in enumerate(engine._slot_rid)}
    seqs = [p + [engine.outputs[r][0]] for p, r in zip(prompts, rids)]
    got = np.stack([logits[slot_of[r]] for r in rids])
    for r in rids:
        engine.cancel(r)
    check(bool(np.isfinite(got).all()), "first-decode logits not finite")
    return got, seqs


def _rel_l2(got, want) -> list[float]:
    import numpy as np

    return [float(np.linalg.norm(g - w) / np.linalg.norm(w))
            for g, w in zip(got, want)]


def check_native(engine, params, cfg, prompts) -> str:
    """First decode step's logits vs an uncached float32 forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T

    got, seqs = first_decode_logits(engine, prompts)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(
            lambda p, t: T.forward(p, cfg32, t)[0][:, -1]
        )(params, jnp.asarray(seqs, jnp.int32)))
    rel = _rel_l2(got, want)
    top1 = [int(np.argmax(g) == np.argmax(w)) for g, w in zip(got, want)]
    check(max(rel) <= NATIVE_RTOL,
          f"native logits rel L2 {max(rel)} > {NATIVE_RTOL}")
    return (f"native vs float32 forward: rel L2 per request {rel} "
            f"(max {max(rel)} <= {NATIVE_RTOL}), top-1 agree "
            f"{sum(top1)}/{len(top1)}")


def check_int4_kernel(engine, seed: int) -> str:
    """int4_matmul compiled for the chip vs ``ref.ref_int4_matmul``, on the
    served layer-0 MLP up-projection nibbles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.int4_matmul import int4_matmul
    from repro.kernels.packed_matmul import DECODE_BLOCK

    packed = _leaf(engine.params, "up")["packed"][0]
    k = 2 * packed.shape[0]
    x = jax.random.randint(jax.random.PRNGKey(seed), (8, k), -128, 128,
                           dtype=jnp.int32).astype(jnp.int8)
    got = np.asarray(int4_matmul(x, packed, block=DECODE_BLOCK,
                                 interpret=False))
    want = np.asarray(ref.ref_int4_matmul(x, packed))
    bad = int((got != want).sum())
    check(bad == 0, f"int4_matmul differs from its reference in {bad} "
                    f"of {got.size} outputs")
    return (f"int4_matmul (interpret=False) at ({x.shape[0]}, {k}) x "
            f"({k}, {packed.shape[1]}) == ref_int4_matmul bit for bit")


def check_dsp_kernel(engine, seed: int) -> str:
    """packed_matmul_prepacked compiled for the chip vs the jnp reference,
    on the served layer-0 MLP up-projection words of the tuned plan: once
    on quantized activations, and once on raw float activations quantized
    in the kernel prologue (the serving call) against the reference's
    staged ``quantize_unsigned``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.quantize import quantize_unsigned
    from repro.kernels import ref
    from repro.kernels.packed_matmul import packed_matmul_prepacked

    leaf = _leaf(engine.params, "up")
    spec = leaf.spec
    words = leaf.words[0]
    wsc = None if leaf.wsc is None else leaf.wsc[0]
    k = words.shape[0] * spec.chunk
    kx, kf = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.randint(kx, (8, k), 0, 1 << spec.bits_a, dtype=jnp.int32)
    xf = jax.random.normal(kf, (8, k), jnp.float32)
    xq = quantize_unsigned(xf, bits=spec.bits_a, axis=-1)
    cases = {
        "quantized": (
            packed_matmul_prepacked(x, words, wsc, spec=spec,
                                    interpret=False), x),
        "fused quantize": (
            packed_matmul_prepacked(xf, words, wsc, spec=spec,
                                    interpret=False, x_scale=xq.scale,
                                    x_zp=xq.zero_point),
            xq.values.astype(jnp.int32)),
    }
    for name, (got, x_u) in cases.items():
        want = np.asarray(ref.ref_packed_matmul_prepacked(
            x_u, ref.PackedWeightWords(words, wsc), spec))
        bad = int((np.asarray(got) != want).sum())
        check(bad == 0, f"packed_matmul_prepacked ({spec.name()}, {name}) "
                        f"differs from its reference in {bad} of "
                        f"{want.size} outputs")
    return (f"packed_matmul_prepacked {spec.name()} (interpret=False) at "
            f"({x.shape[0]}, {k}) x ({k}, {words.shape[-1]}), on quantized "
            "and on float activations (fused quantize) == "
            "ref_packed_matmul_prepacked bit for bit")


def serve_mode(mode: str, args, dev) -> None:
    import jax

    from repro.launch.serve import init_serving_params
    from repro.models.registry import get_config
    from repro.serving import Engine, ServeConfig

    cfg = get_config(ARCH)
    if mode == "dsp_tuned":
        log(f"{mode}: depth cut {cfg.n_layers} -> {DSP_LAYERS} layers "
            "(bf16 weights + int32 words + mr contamination operands of "
            "24 layers exceed one chip's HBM); widths unchanged")
        cfg = dataclasses.replace(cfg, n_layers=DSP_LAYERS)
    prompts = _prompts(cfg, args.requests, args.prompt_len, args.seed)
    params = init_serving_params(cfg, seed=args.seed)
    scfg = ServeConfig(
        n_slots=args.slots, max_len=args.max_len, max_new=args.max_new,
        quant_mode=mode, plan_bits=(4, 4), seed=args.seed,
    )
    packed = mode != "native"
    check(scfg.use_kernel, f"{mode}: use_kernel did not resolve to the "
                           "Pallas kernels on a TPU")

    t0 = time.perf_counter()
    engine = Engine(cfg, params, scfg)
    jax.block_until_ready(engine.params)
    build_s = time.perf_counter() - t0
    if packed:
        del params  # the engine serves its own packed copy
    t0 = time.perf_counter()
    first = engine.generate(prompts, max_new=args.max_new)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = engine.generate(prompts, max_new=args.max_new)
    serve_s = time.perf_counter() - t0
    tokens = list(first.values())
    check(tokens == list(again.values()),
          f"{mode}: greedy tokens differ between two identical passes")
    check(all(1 <= len(t) <= args.max_new for t in tokens),
          f"{mode}: tokens per request {[len(t) for t in tokens]}")
    n_calls = _custom_calls(engine)
    log(f"{mode}: tokens per request {[len(t) for t in tokens]} "
        f"(first request {tokens[0]})")
    log(f"{mode}: build {build_s:.3f}s, compile {first_s - serve_s:.3f}s "
        f"(first pass {first_s:.3f}s minus warm pass), serve "
        f"{serve_s:.3f}s for {sum(map(len, tokens))} tokens")
    log(f"{mode}: {n_calls} tpu_custom_call in the lowered decode step "
        f"(use_kernel={scfg.use_kernel})")
    if packed:
        check(n_calls > 0, f"{mode}: decode step calls no Pallas kernel")
        check_fn = check_int4_kernel if mode == "int4_packed" else \
            check_dsp_kernel
        log(f"{mode}: {check_fn(engine, args.seed)}")
        if mode == "dsp_tuned":
            plans = sorted({r.name for r in engine.plan_table.values()})
            log(f"{mode}: plans served {plans}")
    else:
        log(f"{mode}: {check_native(engine, params, cfg, prompts)}")
        del params
    log(f"{mode}: {_memory(dev)} (peak over the whole process)")
    del engine
    _free()
    log(f"{mode}: engine freed, {_memory(dev)}")


def four_chips(args, dev) -> None:
    import jax
    import numpy as np

    from repro.launch.serve import init_serving_params
    from repro.models.registry import get_config
    from repro.serving import Engine, ReplicaFront, ServeConfig

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}")
    cfg = get_config(ARCH)
    prompts = _prompts(cfg, args.requests, args.prompt_len, args.seed)
    params = init_serving_params(cfg, seed=args.seed)

    def scfg(**kw):
        return ServeConfig(n_slots=args.slots, max_len=args.max_len,
                           max_new=args.max_new, quant_mode="int4_packed",
                           seed=args.seed, **kw)

    def same_logits(name, got) -> None:
        rel = _rel_l2(got, want_logits)
        diff = float(np.abs(got - want_logits).max())
        log(f"{name} first-decode logits vs tp=1: rel L2 per request {rel} "
            f"(max {max(rel)} <= {TP_RTOL}), max abs diff {diff}"
            f"{' (bit-identical)' if diff == 0 else ''}")
        check(max(rel) <= TP_RTOL,
              f"{name} logits rel L2 {max(rel)} > {TP_RTOL} from tp=1")

    engine = Engine(cfg, params, scfg())
    want = list(engine.generate(prompts).values())
    want_logits, _ = first_decode_logits(engine, prompts)
    del engine
    _free()
    log(f"int4_packed tp=1: first request {want[0]}")
    if all(len(set(t)) == 1 for t in want):
        log("int4_packed tp=1: every request repeats one token id (random "
            "weights), so token equality alone shows little; the logits "
            "comparisons below are the check")

    t0 = time.perf_counter()
    tp_cfg = scfg(tp=4)
    engine = Engine(cfg, params, tp_cfg)
    got = list(engine.generate(prompts).values())
    got_logits, _ = first_decode_logits(engine, prompts)
    log(f"int4_packed tp=4 ({time.perf_counter() - t0:.3f}s with build and "
        f"compile; use_kernel={tp_cfg.use_kernel}: the shard_map'd jnp "
        "reference, kernels have no cross-device stage): first request "
        f"{got[0]}")
    same_logits("int4_packed tp=4", got_logits)
    check(got == want, "tp=4 tokens differ from tp=1 tokens")
    log("int4_packed tp=4 tokens == tp=1 tokens")
    del engine
    _free()

    front = ReplicaFront(cfg, params, scfg(), n_replicas=4)
    homes = [
        sorted({d.id for a in jax.tree.leaves(r.params) for d in a.devices()})
        for r in front.replicas
    ]
    log(f"replicas' weight devices {homes}")
    check(homes == [[0], [1], [2], [3]], f"replicas share chips: {homes}")
    out = front.generate(prompts * 2)
    got = [out[g] for g in sorted(out)]
    check(got == want * 2, "replica tokens differ from one engine's")
    log(f"4 replicas served {len(got)} requests on chips "
        f"{sorted({front.replica_of(g) for g in out})}, tokens == tp=1")
    for i, replica in enumerate(front.replicas):
        same_logits(f"replica {i}", first_decode_logits(replica, prompts)[0])
    del front, params
    _free()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    try:
        import jax

        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        log(f"FAIL: the repository's sources are not next to this file ({e})")
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"FAIL: no TPU: JAX's first device is {dev.platform!r}")
        return 1
    cache = enable_compile_cache()
    log(f"device {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {cache}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            four_chips(args, dev)
        else:
            for mode in ("native", "int4_packed", "dsp_tuned"):
                serve_mode(mode, args, dev)
    except SmokeFailure as e:
        log(f"FAIL: {e}")
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
