"""Packed-storage int4 matmul — the production Pallas kernel.

The *memory* translation of DSP-packing density (DESIGN.md §2): weights live
in HBM packed two nibbles per byte (like operands packed into a DSP port),
halving weight bytes moved — the quantity that dominates decode-phase
rooflines.  Nibbles are unpacked inside VMEM with int32 mask/shift/xor
(Mosaic has no int8 vector shifts) and fed to the MXU int8 path
(``preferred_element_type=int32``).

Packed row ``r`` holds weight rows ``2r`` (low nibble) and ``2r + 1`` (high
nibble).  Instead of re-interleaving the two nibble planes into one
(bk, bn) tile — a sublane shuffle Mosaic cannot lower — the wrapper splits
the activation into its even and odd columns and the kernel contracts each
half against its own nibble plane: ``x_even @ lo + x_odd @ hi``.

Grid (M/bm, N/bn, K/bk); the packed weight block is (bk//2, bn) so the HBM
traffic for weights really is half of the int8 kernel's.  On the chip the
activation halves' lane dimension ``bk // 2`` must be a multiple of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["int4_matmul", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = (128, 128, 256)


def _dot(x, w):
    return jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )


def _kernel(xe_ref, xo_ref, wp_ref, out_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    packed = wp_ref[...].astype(jnp.int32)  # (bk//2, bn), in [0, 255]
    # sign-extend each nibble in int32 ((v ^ 8) - 8 maps [0, 15] onto
    # [-8, 7]), then narrow exactly to int8 for the MXU
    lo = (((packed & 0xF) ^ 8) - 8).astype(jnp.int8)
    hi = (((packed >> 4) ^ 8) - 8).astype(jnp.int8)
    out_ref[...] += _dot(xe_ref[...], lo) + _dot(xo_ref[...], hi)


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, (-x.shape[axis]) % mult)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def int4_matmul(
    x_q: jax.Array,
    w_packed: jax.Array,
    block: tuple[int, int, int] = DEFAULT_BLOCK,
    interpret: bool = True,
) -> jax.Array:
    """(M, K) int8 × (K//2, N) packed-nibble uint8 → (M, N) int32.

    Any shape is accepted: M/N/K are zero-padded up to the block grid (zero
    activations and zero nibbles contribute nothing) and the result is
    sliced back to (M, N)."""
    m, k = x_q.shape
    k2, n = w_packed.shape
    assert k == 2 * k2, (k, k2)
    bm, bn, bk = block
    if bk % 2:
        raise ValueError(f"block {block}: bk must be even")
    x_q = _pad_to(_pad_to(x_q, bm, 0), bk, 1)
    w_packed = _pad_to(_pad_to(w_packed, bk // 2, 0), bn, 1)
    (mp, kp), np_ = x_q.shape, w_packed.shape[1]

    half = pl.BlockSpec((bm, bk // 2), lambda i, j, kk: (i, kk))
    out = pl.pallas_call(
        _kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            half,
            half,
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=interpret,
        # names the HLO instruction a device profile shows: the jitted
        # wrapper's name, which the benchmark's kernel readers match
        name="int4_matmul",
    )(x_q[:, 0::2], x_q[:, 1::2], w_packed)
    return out[:m, :n]
