"""Block-size autotuner for the pair-packed Pallas kernel.

The paper picks a packing *shape*; on TPU the other half of the throughput
frontier is the kernel's block shape.  This module sweeps ``(bm, bn, bk)``
candidates for a given spec and problem shape and times the jitted kernel.

Timing is pluggable: pass ``timer=`` any callable with the
``benchmarks.bench_util.time_us`` signature (``timer(fn, warmup=, iters=)``)
— the benchmark harness passes exactly that function — or use the built-in
default, which additionally blocks on the device result so async dispatch
doesn't fake a win.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.packed_matmul import packed_matmul
from ..kernels.ref import PackedDotSpec

__all__ = [
    "BlockTiming",
    "candidate_blocks",
    "autotune_block",
    "autotune_phase_blocks",
    "default_timer",
    "DEFAULT_BLOCKS",
    "DECODE_BLOCKS",
    "PHASE_BLOCKS",
]

# Sweep grid, filtered per spec/problem by candidate_blocks.  Every block
# compiles for the chip: bk a multiple of 256 (the kernel's activation
# halves need 128-lane tiles), bn a multiple of 128, and bm at most 64 (the
# unrolled VPU chunk loop's VMEM temporaries grow with bm).
DEFAULT_BLOCKS = (
    (32, 128, 256),
    (32, 128, 512),
    (32, 256, 256),
    (16, 128, 256),
    (16, 128, 512),
    (64, 128, 256),
)

# Decode-phase sweep grid: a decode step is a GEMV over the slot batch
# (M of 1-16), so M blocks hug the batch instead of padding it up to a
# prefill tile; N/K blocks still sweep the weight-streaming axis.
DECODE_BLOCKS = (
    (8, 128, 256),
    (8, 128, 512),
    (8, 256, 256),
    (16, 128, 256),
)

# The serving engine runs the same kernel in two regimes with very different
# M; each phase is tuned independently and the tuned plan carries one block
# per phase (tuner.PlanReport.block / .decode_block).
PHASE_BLOCKS = {"prefill": DEFAULT_BLOCKS, "decode": DECODE_BLOCKS}


@dataclasses.dataclass(frozen=True)
class BlockTiming:
    block: tuple[int, int, int]
    us_per_call: float


def default_timer(fn: Callable[[], object], warmup: int = 1, iters: int = 3) -> float:
    """``bench_util.time_us``-compatible timer that blocks on the result."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / iters * 1e6


def candidate_blocks(
    spec: PackedDotSpec,
    m: int,
    k: int,
    n: int,
    blocks: Sequence[tuple[int, int, int]] | None = None,
    phase: str = "prefill",
) -> list[tuple[int, int, int]]:
    """Filter the sweep grid to blocks legal for ``spec`` and not absurdly
    oversized for the problem (> 2x padding waste on any axis is dropped,
    unless nothing survives — then the smallest legal block is kept).
    ``phase`` selects the default grid (decode sweeps small-M GEMV blocks)
    when ``blocks`` is not given."""
    if blocks is None:
        blocks = PHASE_BLOCKS[phase]
    legal = [b for b in blocks if b[2] % spec.chunk == 0]
    snug = [
        b for b in legal
        if b[0] <= 2 * m and b[1] <= 2 * n and b[2] <= 2 * k
    ]
    if snug:
        return snug
    if legal:
        return [min(legal, key=lambda b: b[0] * b[1] * b[2])]
    # no candidate's bk holds whole extraction chunks: build one that does
    return [(8 if m <= 8 else 32, 128, math.lcm(256, spec.chunk))]


def autotune_block(
    spec: PackedDotSpec,
    shape: tuple[int, int, int],
    blocks: Sequence[tuple[int, int, int]] | None = None,
    interpret: bool | None = None,
    timer: Callable[..., float] | None = None,
    warmup: int = 1,
    iters: int = 3,
    seed: int = 0,
    phase: str = "prefill",
    prepacked: bool = False,
) -> list[BlockTiming]:
    """Time every candidate block on a ``shape = (m, k, n)`` problem.

    Returns timings sorted fastest-first.  The kernel output is cross-checked
    bit-exact against the first block's result — a mistuned block may only
    be slow, never wrong.  ``phase`` picks the candidate grid when
    ``blocks`` is omitted; ``prepacked=True`` times the serving profile
    (weights packed ONCE outside the timed region, the prepacked kernel
    entry inside it) instead of the pack-per-call kernel."""
    from ..kernels.ops import auto_interpret
    from ..kernels.packed_matmul import packed_matmul_prepacked

    m, k, n = shape
    if interpret is None:
        interpret = auto_interpret()
    if timer is None:
        timer = default_timer
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 1 << spec.bits_a, (m, k)), jnp.int32)
    w = jnp.asarray(
        rng.integers(-(1 << (spec.bits_w - 1)), 1 << (spec.bits_w - 1), (k, n)),
        jnp.int32,
    )
    if prepacked:
        from ..kernels import ref as _ref

        packed = _ref.pack_weight_words(w, spec)
    cands = candidate_blocks(spec, m, k, n, blocks, phase=phase)
    timings: list[BlockTiming] = []
    reference = None
    for block in cands:
        if prepacked:
            def run(block=block):
                return packed_matmul_prepacked(
                    x, packed.words, packed.wsc, spec=spec, block=block,
                    interpret=interpret,
                )
        else:
            def run(block=block):
                return packed_matmul(
                    x, w, spec=spec, block=block, interpret=interpret
                )

        out = np.asarray(run())
        if reference is None:
            reference = out
        else:
            np.testing.assert_array_equal(out, reference)
        timings.append(BlockTiming(block, timer(run, warmup=warmup, iters=iters)))
    return sorted(timings, key=lambda t: t.us_per_call)


def autotune_phase_blocks(
    spec: PackedDotSpec,
    shapes: dict[str, tuple[int, int, int]],
    **kwargs,
) -> dict[str, BlockTiming]:
    """Best block PER SERVING PHASE: ``shapes`` maps a phase name
    ("prefill"/"decode") to its (m, k, n) probe — a chunked-prefill M and a
    slot-batch GEMV M tune very differently, so each phase sweeps its own
    candidate grid and the tuned plan carries one block per phase.

    Times the prepacked serving profile (the entry decode actually runs).
    """
    return {
        phase: autotune_block(
            spec, shape, phase=phase, prepacked=True, **kwargs
        )[0]
        for phase, shape in shapes.items()
    }
