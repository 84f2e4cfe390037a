"""Public wrappers around the Pallas kernels.

Handles shape padding to block multiples, scale/zero-point bookkeeping and
backend dispatch (``interpret=True`` everywhere except real TPUs), and
exposes a float-in/float-out ``packed_linear_apply`` used by the model zoo.

The ``*_prepacked`` entries are the serving decode fast path: weights
arrive as operands packed ONCE at engine build (``core.packed_params``), so
a decode step does no per-call weight packing, no zero-point reduction and
no M-padding to MXU tiles — the historical packed-decode tax was a ~64x
padded GEMV plus a full weight repack per K-step per step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.quantize import (
    quantize_signed,
    quantize_unsigned,
    unsigned_scale,
    zero_point_correction,
)
from . import ref
from .int4_matmul import DEFAULT_BLOCK as INT4_BLOCK, int4_matmul
from .packed_matmul import (
    DECODE_BLOCK,
    default_block_for,
    packed_matmul,
    packed_matmul_prepacked,
)

__all__ = [
    "on_tpu",
    "auto_interpret",
    "packed_matmul_f32",
    "dsp_tuned_matmul_f32",
    "dsp_tuned_matmul_prepacked_f32",
    "int4_matmul_f32",
    "int4_prepacked_matmul_f32",
    "quantized_matmul_ref",
]

from .ref import INT4_EXACT, PackedDotSpec


def on_tpu() -> bool:
    """Whether the default backend is a TPU: there the Pallas kernels are
    compiled and serving runs them; elsewhere they run in interpret mode
    and serving uses the jnp references (plus the f32 grids of
    ``core.packed_params``, which pay off only where integer dots lower to
    scalar loops)."""
    return jax.default_backend() == "tpu"


def auto_interpret() -> bool:
    """Pallas interpret mode everywhere but a real TPU backend."""
    return not on_tpu()


@functools.partial(
    jax.jit, static_argnames=("spec", "block", "interpret", "use_kernel")
)
def packed_matmul_f32(
    x: jax.Array,
    w: jax.Array,
    spec: PackedDotSpec = INT4_EXACT,
    block=None,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """float (M, K) × float (K, N) through the pair-packed integer path.

    Quantizes activations offset-binary unsigned (zero point folded back via
    ``zero_point_correction``) and weights signed per output channel, runs
    the packed integer matmul, and dequantizes.
    """
    xq = quantize_unsigned(x, bits=spec.bits_a, axis=-1)
    wq = quantize_signed(w, bits=spec.bits_w, axis=0)
    # ragged shapes are padded (bit-transparently) inside the compute paths
    if use_kernel:
        acc = packed_matmul(
            xq.values, wq.values, spec=spec,
            block=block or default_block_for(x.shape[0], spec),
            interpret=auto_interpret() if interpret is None else interpret,
        )
    else:
        acc = ref.ref_packed_matmul(xq.values, wq.values, spec=spec)
    acc = acc - zero_point_correction(wq.values, xq.zero_point)[None, :]
    return acc.astype(jnp.float32) * xq.scale * wq.scale


@functools.partial(
    jax.jit, static_argnames=("spec", "block", "interpret", "use_kernel")
)
def dsp_tuned_matmul_f32(
    x: jax.Array,
    w_values: jax.Array,
    w_scale: jax.Array,
    spec: PackedDotSpec,
    block=None,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """float (M, K) × pre-quantized signed (K, N) through a tuned plan.

    The per-call companion of :func:`dsp_tuned_matmul_prepacked_f32` for
    weights that are quantized but not prepacked (weights repacked into
    words on every call) — kept for stacked leaves outside a layer scan and
    for benchmarking the repacking tax itself.
    """
    xq = quantize_unsigned(x, bits=spec.bits_a, axis=-1)
    wv = w_values.astype(jnp.int32)
    if use_kernel:
        acc = packed_matmul(
            xq.values, wv, spec=spec,
            block=block or default_block_for(x.shape[0], spec),
            interpret=auto_interpret() if interpret is None else interpret,
        )
    else:
        acc = ref.ref_packed_matmul(xq.values, wv, spec=spec)
    acc = acc - zero_point_correction(wv, xq.zero_point)[None, :]
    return acc.astype(jnp.float32) * xq.scale * w_scale


@functools.partial(
    jax.jit,
    static_argnames=("spec", "block", "interpret", "use_kernel", "exact_f32"),
)
def dsp_tuned_matmul_prepacked_f32(
    x: jax.Array,
    words: jax.Array,
    wsc: jax.Array | None,
    zp_row: jax.Array,
    w_scale: jax.Array,
    w_f32: jax.Array | None,
    spec: PackedDotSpec,
    block: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
    use_kernel: bool = True,
    exact_f32: bool = False,
) -> jax.Array:
    """float (M, K) × PREPACKED tuned-plan weights → f32 (M, N).

    The serving decode fast path: ``words``/``wsc``/``zp_row``/``w_f32``
    were built once at engine build (``DspTunedLeaf``), so the per-step work
    is activation quantization plus the compute stage — nothing repacks.

    ``exact_f32`` (only legal when the plan is PROVEN exact and the operand
    bound fits the f32 mantissa — the leaf's ``w_f32`` existence encodes
    both) evaluates the identical integer matmul on the f32 GEMM unit:
    bit-for-bit the packed kernel's output, at dense-float speed on
    backends whose integer dots lower to scalar loops.

    With the kernel path, the activation quantize is fused into the kernel
    prologue (``x_scale``/``x_zp``): the int activation tensor never stages
    through HBM, and the result is bit-identical to the reference path's
    staged quantize.
    """
    m = x.shape[0]
    if exact_f32 and w_f32 is not None:
        # quantize_unsigned without the uint8 round-trip (values are exact
        # small integers either way; the clip never binds — |x/scale| is
        # bounded by zp-1 by construction); the f32 GEMM then computes the
        # exact packed-plan matmul — see the docstring
        zp = 1 << (spec.bits_a - 1)
        x_scale = unsigned_scale(x, bits=spec.bits_a, axis=-1)
        q = jnp.round(x / x_scale) + zp
        acc = q @ w_f32  # exact: fits the f32 mantissa
        acc = acc - zp_row.astype(jnp.float32)[None, :]
        return acc * x_scale * w_scale
    if use_kernel:
        # fused-quantize prologue: pass raw f32 + per-row scale to the kernel
        zp = 1 << (spec.bits_a - 1)
        x_scale = unsigned_scale(x, bits=spec.bits_a, axis=-1)
        acc = packed_matmul_prepacked(
            x.astype(jnp.float32), words, wsc, spec=spec,
            block=block or default_block_for(m, spec),
            interpret=auto_interpret() if interpret is None else interpret,
            x_scale=x_scale, x_zp=zp,
        )
    else:
        xq = quantize_unsigned(x, bits=spec.bits_a, axis=-1)
        x_scale = xq.scale
        acc = ref.ref_packed_matmul_prepacked(
            xq.values.astype(jnp.int32), ref.PackedWeightWords(words, wsc),
            spec,
        )
    acc = acc - zp_row[None, :]
    return acc.astype(jnp.float32) * x_scale * w_scale


@functools.partial(jax.jit, static_argnames=("block", "interpret", "use_kernel"))
def int4_matmul_f32(
    x: jax.Array,
    w_packed: jax.Array,
    w_scale: jax.Array,
    block=None,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """float (M, K) × packed int4 (K//2, N) → f32, int8 activations.

    The ref path runs unpadded (an (M, K, N) problem needs no MXU tile
    grid); the kernel pads to a decode-aware block — small-M GEMV blocks
    for decode shapes instead of 128-row tiles that would pad a 2-slot
    decode ~64x in M.
    """
    xq = quantize_signed(x, bits=8, axis=-1)
    if use_kernel:
        if block is None:
            block = (DECODE_BLOCK if x.shape[0] <= DECODE_BLOCK[0]
                     else INT4_BLOCK)
        acc = int4_matmul(
            xq.values, w_packed, block=block,
            interpret=auto_interpret() if interpret is None else interpret,
        )
    else:
        acc = ref.ref_int4_matmul(xq.values, w_packed)
    return acc.astype(jnp.float32) * xq.scale * w_scale


def _quantize_signed_f32(x: jax.Array, bits: int):
    """``quantize_signed`` without the int8 round-trip: the quantized grid
    values are computed (and kept) in f32 — they are exact small integers,
    so the downstream f32 GEMM sees bit-identical operands while decode
    skips two dtype conversions per linear.  The clip is omitted because it
    never binds: ``|x / scale| <= qmax`` by the scale's construction, so
    ``round`` already lands inside the signed grid."""
    qmax = (1 << (bits - 1)) - 1
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    return jnp.round(x / scale), scale


@jax.jit
def int4_prepacked_matmul_f32(
    x: jax.Array,
    w_f32: jax.Array,
    w_scale: jax.Array,
) -> jax.Array:
    """float (M, K) × int4 grid decoded once to f32 (K, N) → f32 (M, N).

    The int4_packed serving fast path: ``w_f32`` holds the nibble grid
    decoded at engine build.  With int8 activations every partial sum is an
    integer below 2**24 (guarded at build via
    ``ref.exact_int_matmul_fits_f32``), so the f32 GEMM computes the exact
    int8×int4 integer matmul — bit-identical to ``ref.ref_int4_matmul`` on
    the stored nibbles — while hitting the dense-float unit.
    """
    q, scale = _quantize_signed_f32(x, bits=8)
    acc = q @ w_f32
    return acc * scale * w_scale


def quantized_matmul_ref(x: jax.Array, w: jax.Array, bits: int = 4) -> jax.Array:
    """Exact-arithmetic quantized matmul (no packing) — accuracy oracle."""
    xq = quantize_unsigned(x, bits=bits, axis=-1)
    wq = quantize_signed(w, bits=bits, axis=0)
    acc = ref.ref_quantized_matmul(xq.values, wq.values)
    acc = acc - zero_point_correction(wq.values, xq.zero_point)[None, :]
    return acc.astype(jnp.float32) * xq.scale * wq.scale
