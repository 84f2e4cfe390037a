"""Quantizers feeding the DSP-packing compute paths.

The packing scheme wants *unsigned* activations and *signed* weights
(paper §III).  Signed activations are handled with an offset-binary zero
point ``zp = 2**(bits-1)``; the resulting constant ``zp * Σ_k w[k, n]`` is
folded out of the matmul once per output channel (``zero_point_correction``).

``fake_quant_*`` are straight-through-estimator (STE) versions for QAT: the
forward pass quantize→dequantizes, the backward pass is the identity inside
the clipping range.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

__all__ = [
    "QuantizedTensor",
    "quantize_signed",
    "quantize_unsigned",
    "unsigned_scale",
    "dequantize",
    "fake_quant_signed",
    "fake_quant_unsigned",
    "zero_point_correction",
]


@dataclasses.dataclass
class QuantizedTensor:
    """Integer payload + per-channel scale (+ zero point for unsigned)."""

    values: jax.Array  # int8 payload (narrow values stored widened)
    scale: jax.Array  # f32, broadcastable against values along `axis`
    bits: int
    zero_point: int = 0  # 0 for signed; 2**(bits-1) for unsigned

    def dequantize(self) -> jax.Array:
        return (self.values.astype(jnp.float32) - self.zero_point) * self.scale


jax.tree_util.register_dataclass(
    QuantizedTensor,
    data_fields=["values", "scale"],
    meta_fields=["bits", "zero_point"],
)


def _absmax_scale(x: jax.Array, axis, qmax: int) -> jax.Array:
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    return jnp.maximum(amax, 1e-8) / qmax


def quantize_signed(x: jax.Array, bits: int = 4, axis=-1) -> QuantizedTensor:
    """Symmetric signed quantization: values in ``[-2^(b-1), 2^(b-1)-1]``."""
    qmax = (1 << (bits - 1)) - 1
    scale = _absmax_scale(x, axis, qmax)
    q = jnp.clip(jnp.round(x / scale), -qmax - 1, qmax).astype(jnp.int8)
    return QuantizedTensor(q, scale, bits=bits, zero_point=0)


def unsigned_scale(x: jax.Array, bits: int = 4, axis=-1) -> jax.Array:
    """The scale :func:`quantize_unsigned` quantizes ``x`` with."""
    return _absmax_scale(x, axis, (1 << (bits - 1)) - 1)


def quantize_unsigned(x: jax.Array, bits: int = 4, axis=-1) -> QuantizedTensor:
    """Offset-binary quantization: values in ``[0, 2^b - 1]``, zp at mid.

    The payload is uint8: an int8 store would saturate the upper half of the
    8-bit offset-binary range (float→int8 conversion clamps at 127, so every
    value above the zero point collapsed — a silent a8 activation bug the
    prepacked kernel's fused-quantize parity check caught)."""
    zp = 1 << (bits - 1)
    scale = unsigned_scale(x, bits, axis)
    q = jnp.clip(jnp.round(x / scale) + zp, 0, (1 << bits) - 1).astype(jnp.uint8)
    return QuantizedTensor(q, scale, bits=bits, zero_point=zp)


def dequantize(q: QuantizedTensor) -> jax.Array:
    return q.dequantize()


def zero_point_correction(w_q: jax.Array, zp: int) -> jax.Array:
    """``zp * Σ_k w[k, n]`` — folded back after an unsigned×signed matmul.

    With ``a_u = a + zp``: ``a·w = a_u·w − zp·Σ w`` per output channel; the
    packed path computes ``a_u·w`` and this term restores the true product.
    """
    return zp * jnp.sum(w_q.astype(jnp.int32), axis=0)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fake_quant_signed(x: jax.Array, bits: int = 4, axis=-1) -> jax.Array:
    q = quantize_signed(x, bits=bits, axis=axis)
    return q.dequantize().astype(x.dtype)


def _fq_signed_fwd(x, bits, axis):
    qmax = (1 << (bits - 1)) - 1
    scale = _absmax_scale(x, axis, qmax)
    mask = (jnp.abs(x) <= scale * (qmax + 1)).astype(x.dtype)
    return fake_quant_signed(x, bits, axis), mask


def _fq_signed_bwd(bits, axis, mask, g):
    return (g * mask,)


fake_quant_signed.defvjp(_fq_signed_fwd, _fq_signed_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def fake_quant_unsigned(x: jax.Array, bits: int = 4, axis=-1) -> jax.Array:
    q = quantize_unsigned(x, bits=bits, axis=axis)
    return q.dequantize().astype(x.dtype)


def _fq_unsigned_fwd(x, bits, axis):
    zp = 1 << (bits - 1)
    scale = _absmax_scale(x, axis, zp - 1)
    mask = (jnp.abs(x) <= scale * zp).astype(x.dtype)
    return fake_quant_unsigned(x, bits, axis), mask


def _fq_unsigned_bwd(bits, axis, mask, g):
    return (g * mask,)


fake_quant_unsigned.defvjp(_fq_unsigned_fwd, _fq_unsigned_bwd)
