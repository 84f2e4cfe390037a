"""PackedLinear: every matmul in the model zoo routes through this module.

One module, five compute modes — the paper's technique is a first-class,
config-selectable feature of the framework rather than a bolt-on:

  * ``native``      — plain dense matmul (bf16/f32), the unquantized baseline
  * ``qat4``/``qat8`` — fake-quant STE on weights (+ optionally activations):
                      differentiable, used for quantization-aware *training*
  * ``int8``        — real int8×int8→int32 arithmetic (MXU-native path)
  * ``int4_packed`` — packed-nibble storage + production Pallas kernel
  * ``dsp_packed``  — the paper's pair-packed wide-multiply path (Pallas),
                      correction scheme selectable via ``PackedDotSpec``
  * ``dsp_tuned``   — per-layer tuned pair-packed plans: weights arrive as
                      ``DspTunedLeaf`` (quantized once at engine build, plan
                      attached as static aux) and each layer runs ITS plan's
                      arithmetic; float leaves under this mode fall back to
                      the native matmul (only packable weights get plans)

Inference-only integer paths raise under differentiation by construction
(they are used inside ``serve_step``).  Params are plain pytrees (plus the
registered ``DspTunedLeaf`` node for tuned weights).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..kernels import ops, ref
from ..kernels.ref import INT4_EXACT, PackedDotSpec
from .quantize import fake_quant_signed, quantize_signed

__all__ = ["LinearSpec", "init_linear", "apply_linear"]

MODES = ("native", "qat4", "qat8", "int8", "int4_packed", "dsp_packed",
         "dsp_tuned")


@dataclasses.dataclass(frozen=True)
class LinearSpec:
    mode: str = "native"
    dsp_spec: PackedDotSpec = INT4_EXACT
    use_kernel: bool = False  # Pallas kernel vs jnp ref (CPU tests use ref)
    act_bits: int | None = None  # fake-quant activations too (QAT)

    def __post_init__(self) -> None:
        if self.mode not in MODES:
            raise ValueError(f"mode {self.mode!r} not in {MODES}")


def init_linear(key, d_in: int, d_out: int, bias: bool = False, dtype=jnp.float32):
    scale = d_in**-0.5
    params = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        params["b"] = jnp.zeros((d_out,), dtype)
    return params


def _flatten_batch(x):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def apply_linear(params, x: jax.Array, spec: LinearSpec = LinearSpec()) -> jax.Array:
    """``x @ w (+ b)`` through the selected compute mode."""
    from .packed_params import (
        is_dsp_tuned_leaf,
        is_packed_leaf,
        materialize_weight,
    )

    w = params["w"]
    mode = spec.mode
    if type(w).__name__ == "TpLinear":
        # tensor-parallel serving: the leaf was mesh-partitioned at engine
        # build (runtime.tp_packed.shard_params_tp); the wrapper carries
        # the partition kind and runs the shard_map'd arithmetic
        from ..runtime.tp_packed import TpLinear, apply_tp_linear

        if isinstance(w, TpLinear):
            x2, lead = _flatten_batch(x.astype(jnp.float32))
            y = apply_tp_linear(w, x2, spec)
            n_out = y.shape[-1]
            y = y.reshape(*lead, n_out).astype(x.dtype)
            if "b" in params:
                y = y + params["b"].astype(y.dtype)
            return y
    if is_dsp_tuned_leaf(w):
        if w.payload.ndim == 2:
            # serving decode path: this layer's tuned plan rides on the leaf
            # (static aux), weights were quantized once at engine build
            x2, lead = _flatten_batch(x.astype(jnp.float32))
            n_out = w.scale.shape[-1]
            if w.prepacked:
                # prepacked fast path: words/zp built once, nothing repacks;
                # proven-exact plans additionally take the f32-GEMM shortcut
                # (bit-identical — see ops.dsp_tuned_matmul_prepacked_f32)
                y = ops.dsp_tuned_matmul_prepacked_f32(
                    x2, w.words, w.wsc, w.zp_row, w.scale, w.w_f32,
                    spec=w.spec, block=w.block_for(x2.shape[0]),
                    use_kernel=spec.use_kernel,
                    exact_f32=w.w_f32 is not None and not spec.use_kernel,
                )
            else:
                y = ops.dsp_tuned_matmul_f32(
                    x2, w.values, w.scale, spec=w.spec,
                    block=w.block,
                    use_kernel=spec.use_kernel,
                )
            y = y.reshape(*lead, n_out).astype(x.dtype)
        else:
            # stacked leaves outside a layer scan: dequantize at use
            y = x @ materialize_weight(w, x.dtype)
        if "b" in params:
            y = y + params["b"].astype(y.dtype)
        return y
    if is_packed_leaf(w):
        if mode == "int4_packed" and w["packed"].ndim == 2:
            x2, lead = _flatten_batch(x.astype(jnp.float32))
            if "w_f32" in w and not spec.use_kernel:
                # prepacked fast path: the nibble grid was decoded once at
                # engine build; the f32 GEMM computes the exact int8×int4
                # matmul (bit-identical to the unpack+int-dot path)
                y = ops.int4_prepacked_matmul_f32(x2, w["w_f32"], w["scale"])
            else:
                # run the production packed kernel straight off the stored
                # nibbles — no per-call repack
                y = ops.int4_matmul_f32(
                    x2, w["packed"], w["scale"], use_kernel=spec.use_kernel
                )
            y = y.reshape(*lead, w["packed"].shape[-1]).astype(x.dtype)
        else:
            # packed-storage representation under a float compute mode:
            # nibbles live in HBM, dequantize at the point of use (fused
            # into the matmul on TPU)
            y = x @ materialize_weight(w, x.dtype)
        if "b" in params:
            y = y + params["b"].astype(y.dtype)
        return y
    if mode in ("native", "dsp_tuned"):
        # dsp_tuned reaching a float leaf means the weight was not packable
        # (tiny, odd-shaped, embedding): serve it natively
        y = x @ w.astype(x.dtype)
    elif mode in ("qat4", "qat8"):
        bits = 4 if mode == "qat4" else 8
        wq = fake_quant_signed(w.astype(jnp.float32), bits, 0).astype(x.dtype)
        xq = (
            fake_quant_signed(x.astype(jnp.float32), spec.act_bits, -1).astype(x.dtype)
            if spec.act_bits
            else x
        )
        y = xq @ wq
    elif mode == "int8":
        x2, lead = _flatten_batch(x.astype(jnp.float32))
        xq = quantize_signed(x2, bits=8, axis=-1)
        wq = quantize_signed(w.astype(jnp.float32), bits=8, axis=0)
        acc = ref.ref_quantized_matmul(xq.values, wq.values)
        y = (acc.astype(jnp.float32) * xq.scale * wq.scale).reshape(
            *lead, w.shape[1]
        ).astype(x.dtype)
    elif mode == "int4_packed":
        x2, lead = _flatten_batch(x.astype(jnp.float32))
        wq = quantize_signed(w.astype(jnp.float32), bits=4, axis=0)
        packed = ref.pack_int4_weights(wq.values)
        y = ops.int4_matmul_f32(
            x2, packed, wq.scale, use_kernel=spec.use_kernel
        ).reshape(*lead, w.shape[1]).astype(x.dtype)
    elif mode == "dsp_packed":
        x2, lead = _flatten_batch(x.astype(jnp.float32))
        y = ops.packed_matmul_f32(
            x2, w.astype(jnp.float32), spec=spec.dsp_spec,
            use_kernel=spec.use_kernel,
        ).reshape(*lead, w.shape[1]).astype(x.dtype)
    else:  # pragma: no cover
        raise AssertionError(mode)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y
