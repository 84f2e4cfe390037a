"""Flash-attention forward Pallas kernel (prefill/serving hot spot).

Online-softmax over KV tiles held in VMEM: grid (B·H, S/bq); each program
streams K/V in ``bk``-sized tiles through VMEM (pl.ds slices), carrying the
running (max, denom, acc) in VREGs — the S×S score matrix never exists.
Causal masking prunes whole tiles past the diagonal.  Training uses the
graph-level chunked attention (`models/layers.py`) for autodiff; this
kernel is the serving-side fast path, validated in interpret mode against
the pure-jnp oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention", "ref_attention"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, seq: int, scale: float):
    iq = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale  # (bq, hd)
    hd = q.shape[-1]
    q_pos = iq * bq + jax.lax.iota(jnp.int32, bq)

    def body(ik, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(ik * bk, bk), :].astype(jnp.float32)  # (bk, hd)
        v = v_ref[pl.ds(ik * bk, bk), :].astype(jnp.float32)
        s = q @ k.T  # (bq, bk)
        k_pos = ik * bk + jax.lax.iota(jnp.int32, bk)
        s = jnp.where(k_pos[None, :] <= q_pos[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    # causal pruning: only tiles up to the diagonal of this q block
    n_tiles = (iq + 1) * bq // bk
    init = (
        jnp.full((bq,), NEG_INF, jnp.float32),
        jnp.zeros((bq,), jnp.float32),
        jnp.zeros((bq, hd), jnp.float32),
    )
    m, l, acc = jax.lax.fori_loop(0, n_tiles, body, init)
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """Causal attention.  q,k,v: (B, H, S, hd) → (B, H, S, hd)."""
    b, h, s, hd = q.shape
    assert s % bq == 0 and s % bk == 0 and bq % bk == 0
    scale = hd**-0.5
    qf = q.reshape(b * h, s, hd)
    kf = k.reshape(b * h, s, hd)
    vf = v.reshape(b * h, s, hd)
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, seq=s, scale=scale),
        grid=(b * h, s // bq),
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s, hd), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, hd), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, hd), q.dtype),
        interpret=interpret,
        # names the HLO instruction a device profile shows: the jitted
        # wrapper's name, which the benchmark's kernel readers match
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, h, s, hd)


def ref_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Pure-jnp causal attention oracle."""
    b, h, s, hd = q.shape
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * hd**-0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v.astype(jnp.float32)).astype(q.dtype)
