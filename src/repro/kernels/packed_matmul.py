"""Pair-packed "DSP-sim" matmul — the paper's technique as a Pallas kernel.

TPU adaptation of DSP-Packing (DESIGN.md §2): the DSP48E2's wide multiplier
becomes the VPU's 32-bit integer multiply lanes; the 48-bit accumulator
becomes int32 accumulation with the paper's δ-padding governing how many
packed products are accumulated (``spec.n_pairs``) between field
extractions.  One int32 multiply computes TWO narrow products (the pair's
dot-product contribution lands in the middle bit field), halving multiply
count for sub-8-bit operands.

The kernel dispatches ANY legal :class:`~repro.kernels.ref.PackedDotSpec`
(arbitrary operand widths, n_pairs counts, correction schemes and multi-DSP
column counts — the plans the ``repro.tuning`` enumerator emits), not just
the int4 presets.  ``spec.n_columns > 1`` spreads one dot product across
several packed int32 words: each activation bit-slice drives its own
packed-word stream against the shared packed weights, fields are extracted
per column and recombined by shifted int32 summation (the wide-datapath
related work's column decomposition) — this is what lifts the int32
accumulator ceiling to exact a8w8 / a8w4 plans.
Extraction semantics live in ``ref.extract_accumulated_field``, shared with
the jnp oracle, so kernel and reference are bit-identical by construction.

Correctness modes mirror the paper exactly:
  * ``naive``   — biased floor extraction (Xilinx white-paper semantics, §V)
  * ``full``    — round-half-up, bit-exact vs the integer matmul (§V-A)
  * ``mr``      — overpacked spacing + MSB restore from cheap LSBs (§VI-B)
  * ``mr+full`` — MSB restore and round-half-up (beyond-paper combination)

Layout: grid (M/bm, N/bn, K/bk); tiles in VMEM; the int32 output block
doubles as the accumulator across K steps (revisited output block).  Ragged
M/N/K are zero-padded to the block grid internally (zero operand pairs are
bit-transparent in every scheme) and the true (M, N) slice is returned.

Chip formulation.  The MXU has no int32 matmul, so the wide multiply runs
on the VPU: each packed activation word (one lane column, broadcast across
the tile) times the matching packed weight-word row, accumulated per
extraction chunk.  Pairs are formed without any in-kernel lane shuffle: the
wrapper splits the activation into its even and odd K columns, and the
kernel builds the words elementwise as ``a_even + (a_odd << p)``.  The
activation halves' lane dimension is ``bk // 2``, which on the chip must be
a multiple of 128 (``default_block_for`` picks such blocks).  With the
activation quantize fused into the kernel (``x_scale``/``x_zp``), the
wrapper splits the raw float activation and each half tile is quantized in
the prologue with the row's scale, exactly as ``quantize_unsigned`` does;
its K padding is -inf, which quantizes to the transparent 0.  The jnp
reference computes the same wrapping int32 sums with a batched
``dot_general``; extraction is shared code, so kernel and reference are
bit-identical (int32 addition is associative mod 2**32).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from .ref import PackedDotSpec, INT4_EXACT

__all__ = [
    "packed_matmul",
    "packed_matmul_prepacked",
    "DEFAULT_BLOCK",
    "DECODE_BLOCK",
    "default_block_for",
]

# (bm, bn, bk): bk = 256 gives the activation halves a 128-lane tile.  The
# VPU wide multiply gains nothing from tall M tiles, while the unrolled
# chunk loop's VMEM temporaries grow with bm (bm=128 overflows the 16 MiB
# scoped VMEM of a v5e for two-column mr plans at prefill).
DEFAULT_BLOCK = (32, 128, 256)
# Decode GEMVs carry a handful of rows (the serving slot count); a 128-row
# M block would pad them ~16-64x.  The small-M default keeps the grid square
# in N/K while the M axis hugs the real batch.
DECODE_BLOCK = (8, 128, 256)


def default_block_for(m: int, spec: PackedDotSpec | None = None):
    """Phase-appropriate default block: small-M GEMV blocks for decode-sized
    ``m``, the general default otherwise.  ``spec`` (when given) bumps
    ``bk`` to the least common multiple with one extraction chunk, so every
    K tile holds whole chunks and keeps its 128-lane halves."""
    block = DECODE_BLOCK if m <= DECODE_BLOCK[0] else DEFAULT_BLOCK
    if spec is not None and block[2] % spec.chunk:
        block = (block[0], block[1], math.lcm(block[2], spec.chunk))
    return block


def _tile_matmul(xe, xo, w_ref, wsc_ref, spec: PackedDotSpec):
    """(bm, bk/2) even/odd activation columns × (bkc, n_pairs, bn) packed
    weight words → (bm, bn) int32, on the VPU.

    Per column slice and extraction chunk: accumulate ``n_pairs`` wide
    products (activation word column ⊗ weight word row), extract the
    middle field (shared with the reference) and sum the fields."""
    bkc, n_pairs, _ = w_ref.shape
    mask = ref.contamination_mask(spec) if spec.uses_mr else 0
    acc = None
    for j in range(spec.n_columns):
        ae = ref.slice_column(xe, spec, j)
        ao = ref.slice_column(xo, spec, j)
        a = ae + (ao << spec.p)
        col = None
        for c in range(bkc):
            w_c = w_ref[c]  # (n_pairs, bn)
            w_even = wsc_ref[c, :, 0, :] & mask if spec.uses_mr else None
            partial = contam = None
            for t in range(n_pairs):
                i = c * n_pairs + t
                prod = a[:, i:i + 1] * w_c[t:t + 1, :]
                partial = prod if partial is None else partial + prod
                if spec.uses_mr:
                    term = (ao[:, i:i + 1] & mask) * w_even[t:t + 1, :]
                    contam = term if contam is None else contam + term
            if spec.uses_mr:
                contam = contam & mask
            field = ref.extract_accumulated_field(partial, spec, contam)
            col = field if col is None else col + field
        shift = spec.column_shift(j)
        col = col << shift if shift else col
        acc = col if acc is None else acc + col
    return acc


def _quantize_tile(x, scale, zp: int):
    """Fused activation-quantize prologue: f32 tile → offset-binary ints.

    The same ops as ``core.quantize.quantize_unsigned`` with the per-row
    scale of the FULL row (an (m, 1) reduction made once outside), so
    quantizing tile by tile is exactly the staged quantize and the int
    activation tensor never round-trips through HBM."""
    q = jnp.round(x / scale) + zp
    return jnp.clip(q, 0, 2 * zp - 1).astype(jnp.int32)


def _kernel(xe_ref, xo_ref, w_ref, *rest, spec: PackedDotSpec,
            x_zp: int | None):
    """One (bm, bk) × (bk//chunk, n_pairs, bn) step; accumulates into the
    revisited output block.  Operands after the words: the mr plan's
    ``wsc``, then (fused quantize) the (bm, 1) row scale, then the output."""
    *rest, out_ref = rest
    scale_ref = rest.pop() if x_zp is not None else None
    wsc_ref = rest.pop() if spec.uses_mr else None

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    if scale_ref is None:
        xe = xe_ref[...].astype(jnp.int32)
        xo = xo_ref[...].astype(jnp.int32)
    else:
        scale = scale_ref[...]
        xe = _quantize_tile(xe_ref[...], scale, x_zp)
        xo = _quantize_tile(xo_ref[...], scale, x_zp)
    out_ref[...] += _tile_matmul(xe, xo, w_ref, wsc_ref, spec)


def _pad_axis(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(
    jax.jit, static_argnames=("spec", "block", "interpret")
)
def packed_matmul(
    x_u: jax.Array,
    w_s: jax.Array,
    spec: PackedDotSpec = INT4_EXACT,
    block: tuple[int, int, int] = DEFAULT_BLOCK,
    interpret: bool = True,
) -> jax.Array:
    """(M, K) unsigned × (K, N) signed → (M, N) int32 via pair packing.

    The per-call entry: packs the weights (``ref.pack_weight_words``) and
    runs the prepacked kernel.  Any shape is accepted: M/N/K are
    zero-padded up to the block grid and the result is sliced back to
    (M, N).  ``block[2]`` must be a multiple of ``spec.chunk`` so every K
    tile holds whole extraction groups.
    """
    assert x_u.shape[1] == w_s.shape[0], (x_u.shape, w_s.shape)
    if block[2] % spec.chunk:
        raise ValueError(
            f"block bk={block[2]} must be a multiple of spec.chunk="
            f"{spec.chunk} ({spec.name()})"
        )
    packed = ref.pack_weight_words(w_s, spec)
    return packed_matmul_prepacked(
        x_u, packed.words, packed.wsc, spec=spec, block=block,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("spec", "block", "interpret", "x_zp")
)
def packed_matmul_prepacked(
    x: jax.Array,
    words: jax.Array,
    wsc: jax.Array | None = None,
    spec: PackedDotSpec = INT4_EXACT,
    block: tuple[int, int, int] | None = None,
    interpret: bool = True,
    x_scale: jax.Array | None = None,
    x_zp: int | None = None,
) -> jax.Array:
    """(M, K) activations × prepacked weight words → (M, N) int32.

    The serving-side kernel entry: weights arrive as
    :func:`ref.pack_weight_words` output (packed ONCE at engine build), so
    no K-step ever rebuilds ``w_words`` or the ``wsc`` contamination stream.
    Bit-identical to ``ref.ref_packed_matmul_prepacked``.

    Without ``x_scale``/``x_zp``, ``x`` holds offset-binary unsigned ints
    (any int dtype).  With them the activation quantize is fused into the
    kernel prologue: ``x`` is the raw float activation and ``x_scale`` its
    per-row quantization scale ((M, 1), from the row absmax over the FULL
    K), so decode stages no int activation tensor through HBM.
    """
    m, k = x.shape
    n_chunks, n_pairs, n = words.shape
    kw = n_chunks * spec.chunk
    if k > kw:
        raise ValueError(f"activation K={k} exceeds packed weights' K={kw}")
    if (x_scale is None) != (x_zp is None):
        raise ValueError("fused quantize needs both x_scale and x_zp")
    if spec.uses_mr and wsc is None:
        raise ValueError(
            f"{spec.name()} is an mr plan: packed_matmul_prepacked needs "
            "the wsc contamination operands from pack_weight_words"
        )
    if block is None:
        block = default_block_for(m, spec)
    bm, bn, bk = block
    if bk % spec.chunk:
        raise ValueError(
            f"block bk={bk} must be a multiple of spec.chunk={spec.chunk} "
            f"({spec.name()})"
        )
    # One K grid covers both operands: a multiple of bk no smaller than
    # either the activation's K or the words' K (an x shorter than the
    # packed weights, e.g. a truncated activation, pads up to the words; a
    # bk-rounded x pads the words with zero chunks — both bit-transparent).
    kp = -(-max(k, kw) // bk) * bk
    if x_zp is None:
        x = jnp.pad(x, ((0, 0), (0, kp - k)))
    else:
        # a raw activation pads with -inf, which the prologue quantizes to
        # the transparent 0 (a 0.0 would quantize to the zero point)
        x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, kp - k)),
                    constant_values=-jnp.inf)
    x = _pad_axis(x, bm, 0)
    if kp > kw:
        pad_chunks = (kp - kw) // spec.chunk
        words = jnp.pad(words, ((0, pad_chunks), (0, 0), (0, 0)))
        if wsc is not None:
            wsc = jnp.pad(wsc, ((0, pad_chunks), (0, 0), (0, 0), (0, 0)))
    words = _pad_axis(words, bn, 2)
    mp = x.shape[0]
    np_ = words.shape[2]
    bkc = bk // spec.chunk  # word-chunks per K step

    half = pl.BlockSpec((bm, bk // 2), lambda i, j, kk: (i, kk))
    in_specs = [
        half,
        half,
        pl.BlockSpec((bkc, n_pairs, bn), lambda i, j, kk: (kk, 0, j)),
    ]
    operands = [x[:, 0::2], x[:, 1::2], words]
    if spec.uses_mr:
        in_specs.append(
            pl.BlockSpec((bkc, n_pairs, 2, bn), lambda i, j, kk: (kk, 0, 0, j))
        )
        operands.append(_pad_axis(wsc, bn, 3))
    if x_zp is not None:
        # padded rows get scale 1, so they never divide by zero
        pad_m = mp - x_scale.shape[0]
        in_specs.append(pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)))
        operands.append(jnp.pad(x_scale.astype(jnp.float32),
                                ((0, pad_m), (0, 0)), constant_values=1.0))
    out = pl.pallas_call(
        functools.partial(_kernel, spec=spec, x_zp=x_zp),
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=interpret,
        # names the HLO instruction a device profile shows: the jitted
        # wrapper's name, which the benchmark's kernel readers match
        name="packed_matmul_prepacked",
    )(*operands)
    return out[:m, :n]
