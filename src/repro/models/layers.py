"""Shared transformer layers (pure JAX, functional, pytree params).

Every projection goes through ``PackedLinear`` so the paper's packed
low-precision arithmetic is selectable per-model via ``cfg.quant``.

KV caches:
  * full attention — cache shape (B, S_max, n_kv, hd), written at ``pos``.
  * sliding-window (SWA) — ring buffer of ``window`` slots written at
    ``pos % window``; decode attends over at most ``window`` keys, making
    long-context decode O(window) (sub-quadratic — DESIGN.md §5).
  * paged — physical pages (n_pages, page_size, n_kv, hd) shared by every
    lane; a per-lane ``page_table`` (B, max_blocks) maps logical block
    ``pos // page_size`` to its physical page.  Writes scatter through the
    table (OOB sentinel entries drop the write — the serving engine masks
    lanes by handing them an all-invalid table row), reads gather the
    lane's logical view back and attend with the same validity mask as the
    dense cache, so paged and dense decode are token-identical
    (``serving.paged_cache`` owns the allocation).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..core.packed_linear import LinearSpec, apply_linear, init_linear
from ..runtime.act_sharding import constrain
from .config import ModelConfig

Params = dict[str, Any]

NEG_INF = -1e9  # mask value safe in bf16


# ---- norms ---------------------------------------------------------------


def init_rmsnorm(d: int, dtype=jnp.float32) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    # Variance is accumulated in f32 WITHOUT materializing an f32 copy of x
    # (a (B,S,D) f32 intermediate would double the residual-stream collective
    # bytes under TP — EXPERIMENTS.md §Perf iteration 2); the normalization
    # itself runs in the compute dtype.
    var = (
        jnp.einsum("...d,...d->...", x, x, preferred_element_type=jnp.float32)
        / x.shape[-1]
    )
    scale = jax.lax.rsqrt(var + eps)[..., None].astype(x.dtype)
    return x * scale * params["scale"].astype(x.dtype)


# ---- rotary embeddings -----------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) or (S,).

    Angles are formed in f32 (huge positions at 500k context), but the
    rotation itself runs in the compute dtype: an f32 rotation would
    materialize f32 (B,S,H,hd) tensors whose gathers/cotangents dominate
    TP collective bytes (EXPERIMENTS.md §Perf iteration 2).
    """
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    if angles.ndim == 2:  # (S, hd/2) -> broadcast over batch
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# ---- attention -------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.hd
    return {
        "wq": init_linear(ks[0], d, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": init_linear(ks[1], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": init_linear(ks[2], d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": init_linear(ks[3], cfg.n_heads * hd, d, dtype=dtype),
    }


def _split_heads(x, n, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n, hd)


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return x
    b, s, kv, hd = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (b, s, kv, n_rep, hd)
    ).reshape(b, s, kv * n_rep, hd)


def attention(
    params: Params,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    cache: Params | None = None,
    causal: bool = True,
    kv_x: jax.Array | None = None,
    page_table: jax.Array | None = None,
) -> tuple[jax.Array, Params | None]:
    """GQA attention. ``cache=None`` → full-sequence (train/prefill).

    ``kv_x`` switches to cross-attention (whisper decoder): K/V come from
    ``kv_x`` and neither causality nor cache updates apply to the source.
    A paged cache (``pages_k``/``pages_v`` leaves) needs ``page_table``
    (B, max_blocks) int32 mapping each lane's logical blocks to physical
    pages; entries == n_pages mark unallocated blocks / masked lanes.
    """
    b, s, _ = x.shape
    hd, nh, nkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    spec = cfg.quant

    if "wqkv" in params:
        # engine-build fused projection (packed_params.fuse_projection_weights):
        # one GEMV for q/k/v per decode step; per-output-channel quantization
        # makes the fused matmul bit-identical per column to the unfused one.
        # Cross-attention never fuses (q and k/v read different inputs).
        assert kv_x is None, "fused qkv is self-attention only"
        qkv = apply_linear(params["wqkv"], x, spec)
        qe = nh * hd
        q, k, v = jnp.split(qkv, (qe, qe + nkv * hd), axis=-1)
        q = constrain(_split_heads(q, nh, hd), "heads")
        k = constrain(_split_heads(k, nkv, hd), "heads")
        v = constrain(_split_heads(v, nkv, hd), "heads")
    else:
        q = constrain(_split_heads(apply_linear(params["wq"], x, spec), nh, hd), "heads")
        src = x if kv_x is None else kv_x
        k = constrain(_split_heads(apply_linear(params["wk"], src, spec), nkv, hd), "heads")
        v = constrain(_split_heads(apply_linear(params["wv"], src, spec), nkv, hd), "heads")

    if kv_x is None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    if cache is not None and "pages_k" in cache:
        # named scopes are HLO metadata only: a device profile attributes
        # the paged path's time to its KV write, KV gather and scores
        with jax.named_scope("paged_attention"):
            out, new_cache = _paged_attention(
                q, k, v, cache, cfg, positions, page_table, x.dtype
            )
    else:
        out, new_cache = _dense_attention(
            q, k, v, cache, cfg, positions, causal, kv_x, x.dtype
        )
    out = out.reshape(b, s, nh * hd)
    return apply_linear(params["wo"], out, spec), new_cache


def _paged_attention(q, k, v, cache, cfg, positions, page_table, dtype):
    """Attention over a paged/block KV cache: scatter this call's K/V
    through the lane's page table, gather the logical view back, attend
    with the dense validity mask.  Unallocated blocks and masked lanes
    carry the OOB sentinel (== n_pages): their writes are DROPPED (JAX OOB
    scatter semantics) and their gathered junk is masked to NEG_INF, whose
    exp underflows to exactly 0 — so paged attention is bit-identical to
    the dense cache over the valid positions."""
    assert page_table is not None, "paged cache needs a page_table"
    b, s, _, hd = q.shape
    nkv = cfg.n_kv_heads
    pages_k, pages_v = cache["pages_k"], cache["pages_v"]
    n_pages, ps = pages_k.shape[0], pages_k.shape[1]
    max_blocks = page_table.shape[1]
    if positions.ndim == 2:
        row_pos = positions[:, 0]
    else:
        row_pos = jnp.broadcast_to(positions.reshape(-1)[:1], (b,))
    pos = row_pos[:, None] + jnp.arange(s)[None]          # (B, S)
    if cfg.sliding_window:
        # ring-buffer pages: the logical slot wraps at the window, so a
        # lane's pool is bounded by ceil(window/page_size) pages.  The
        # serving engines keep chunk-1 prefill for sliding windows
        # (chunked prefill over a ring overwrites slots still needed by
        # earlier in-chunk queries), so s == 1 whenever wrapping can
        # occur.
        slot = pos % cfg.sliding_window
    else:
        slot = pos
    with jax.named_scope("kv_write"):
        blk = slot // ps
        page = jnp.take_along_axis(
            page_table, jnp.clip(blk, 0, max_blocks - 1), axis=1
        )
        # positions past the logical window must not clamp into a live
        # block: force them to the drop sentinel
        page = jnp.where(blk < max_blocks, page, n_pages)
        off = slot % ps
        pages_k = pages_k.at[page, off].set(
            k.astype(pages_k.dtype), mode="drop"
        )
        pages_v = pages_v.at[page, off].set(
            v.astype(pages_v.dtype), mode="drop"
        )
    new_cache = {"pages_k": pages_k, "pages_v": pages_v}
    # gather the lane's logical view (invalid entries clamp to junk
    # pages — masked below exactly like unwritten dense positions)
    window = max_blocks * ps
    with jax.named_scope("kv_gather"):
        k = pages_k[page_table].reshape(b, window, nkv, hd)
        v = pages_v[page_table].reshape(b, window, nkv, hd)
    cache_positions = jnp.arange(window)
    qidx = jnp.arange(s)
    if cfg.sliding_window:
        # every written ring slot is in-window (dense ring branch
        # semantics); gathered slots past the ring are never written
        ring = cfg.sliding_window
        valid = (
            (cache_positions[None, None, :] <= slot[:, :, None])
            | (pos[:, :, None] >= ring)
        ) & (cache_positions[None, None, :] < ring)
    else:
        valid = (
            cache_positions[None, None, :]
            <= row_pos[:, None, None] + qidx[None, :, None]
        )
    return _scores(q, k, v, valid, dtype, decode=True), new_cache


def _dense_attention(q, k, v, cache, cfg, positions, causal, kv_x, dtype):
    """Attention over a dense cache window (decode, or cached chunked
    prefill), or over this call's own K/V when ``cache`` is None."""
    b, s, nh, hd = q.shape
    nkv = cfg.n_kv_heads
    new_cache = None
    if cache is not None:
        # decode (s==1) or cached chunked prefill (s>1, full attention only):
        # write K/V at each row's own position, attend over the cache.  Rows
        # (serving slots) may sit at different depths, so writes and masks
        # are per-row (vmapped update slice).
        window = cache["k"].shape[1]
        if positions.ndim == 2:
            row_pos = positions[:, 0]
        else:
            row_pos = jnp.broadcast_to(positions.reshape(-1)[:1], (b,))
        slot = row_pos % window if cfg.sliding_window else row_pos
        upd = lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (p, 0, 0))
        k_all = jax.vmap(upd)(cache["k"], k.astype(cache["k"].dtype), slot)
        v_all = jax.vmap(upd)(cache["v"], v.astype(cache["v"].dtype), slot)
        new_cache = {"k": k_all, "v": v_all}
        k, v = k_all, v_all
        cache_positions = jnp.arange(window)
        qidx = jnp.arange(s)
        if cfg.sliding_window:
            # ring buffer (decode): every slot written so far is in-window
            valid = (cache_positions[None, :] <= slot[:, None]) | (
                row_pos[:, None] >= window
            )
            valid = jnp.broadcast_to(valid[:, None, :], (b, s, window))
        else:
            valid = (
                cache_positions[None, None, :]
                <= row_pos[:, None, None] + qidx[None, :, None]
            )
    elif causal:
        ii = positions if positions.ndim == 2 else positions[None]
        qi = ii[:, :, None]
        ki = ii[:, None, :]
        valid = ki <= qi
        if cfg.sliding_window:
            valid &= ki > qi - cfg.sliding_window
    else:
        valid = None

    use_chunked = (
        cache is None
        and kv_x is None
        and causal
        and cfg.attention_chunk
        and s > cfg.attention_chunk
        and s % cfg.attention_chunk == 0
    )
    if use_chunked:
        with jax.named_scope("scores"):
            out = _chunked_causal_attention(
                q, _repeat_kv(k, nh // nkv), _repeat_kv(v, nh // nkv),
                positions, cfg.attention_chunk, cfg.sliding_window,
            )
        return out, new_cache
    return _scores(q, k, v, valid, dtype, decode=cache is not None), new_cache


def _scores(q, k, v, valid, dtype, decode: bool):
    """Softmax(QK^T / sqrt(hd) + mask) V, with positions outside ``valid``
    (B, S, K) bool masked to NEG_INF (``None``: attend everywhere).

    ``k``/``v`` keep their own ``n_kv_heads``: with R = n_heads / n_kv_heads
    > 1 the query is grouped as (B, S, G, R, hd), so query head h reads KV
    head h // R (``_repeat_kv``'s order) and no repeated copy of the KV
    window is ever written."""
    b, s, nh, hd = q.shape
    r = nh // k.shape[2]
    with jax.named_scope("scores"):
        if r > 1:
            q = q.reshape(b, s, k.shape[2], r, hd)
            qk, pv = "bqgrd,bkgd->bgrqk", "bgrqk,bkgd->bqgrd"
        else:
            qk, pv = "bqhd,bkhd->bhqk", "bhqk,bkhd->bqhd"
        scores = jnp.einsum(qk, q, k).astype(jnp.float32) * hd**-0.5
        if decode:
            scores = constrain(scores, "scores_decode")
        if valid is not None:
            # (B, S, K) over the head axes of (B, [G, R | H], S, K)
            mask = jnp.where(valid, 0.0, NEG_INF)
            scores = scores + jnp.expand_dims(
                mask, tuple(range(1, scores.ndim - 2))
            )
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        return jnp.einsum(pv, probs, v).reshape(b, s, nh, hd)


def _chunked_causal_attention(q, k, v, positions, chunk: int, window: int | None):
    """Online-softmax (flash-style) causal attention, O(S·chunk) memory.

    Scans KV chunks with running (max, denom, acc) — the S×S f32 score
    matrix is never materialized, which is what makes the 4k/32k train and
    prefill cells fit HBM (EXPERIMENTS.md §Perf iteration 4).  Positions
    must be the standard arange layout (asserted by the caller's shapes).
    """
    b, s, h, hd = q.shape
    scale = hd**-0.5
    n_chunks = s // chunk
    q_pos = jnp.arange(s)
    kc = k.reshape(b, n_chunks, chunk, h, hd).swapaxes(0, 1)
    vc = v.reshape(b, n_chunks, chunk, h, hd).swapaxes(0, 1)

    def body(carry, xs):
        m, l, acc = carry  # (B,H,S), (B,H,S), (B,H,S,hd)
        k_i, v_i, idx = xs
        k_pos = idx * chunk + jnp.arange(chunk)
        scores = (
            jnp.einsum("bqhd,bkhd->bhqk", q, k_i).astype(jnp.float32) * scale
        )  # (B,H,S,chunk)
        ok = k_pos[None, :] <= q_pos[:, None]
        if window:
            ok &= k_pos[None, :] > q_pos[:, None] - window
        scores = jnp.where(ok[None, None], scores, NEG_INF)
        m_i = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, m_i)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_i.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((b, h, s), NEG_INF, jnp.float32),
        jnp.zeros((b, h, s), jnp.float32),
        jnp.zeros((b, h, s, hd), jnp.float32),
    )
    # checkpoint: the backward pass recomputes the (B,H,S,chunk) score block
    # instead of storing one per chunk (flash-attention memory profile);
    # full unroll keeps XLA cost analysis exact (loop bodies count once).
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), init, (kc, vc, jnp.arange(n_chunks)),
        unroll=n_chunks,
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)  # (B,S,H,hd)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    window = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    shape = (batch, window, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_paged_kv_cache(
    cfg: ModelConfig, n_pages: int, page_size: int, dtype=jnp.bfloat16
):
    """Physical page pool for one attention layer: every serving lane's
    K/V lives in fixed-size pages mapped through a per-lane page table
    (``serving.paged_cache.PageAllocator`` owns the mapping)."""
    shape = (n_pages, page_size, cfg.n_kv_heads, cfg.hd)
    return {"pages_k": jnp.zeros(shape, dtype), "pages_v": jnp.zeros(shape, dtype)}


# ---- MLP -------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, dtype=jnp.float32, d_ff: int | None = None) -> Params:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "up": init_linear(ks[0], d, f, dtype=dtype),
        "gate": init_linear(ks[1], d, f, dtype=dtype),
        "down": init_linear(ks[2], f, d, dtype=dtype),
    }


def mlp(params: Params, x: jax.Array, spec: LinearSpec) -> jax.Array:
    if "upgate" in params:
        # engine-build fused up|gate (packed_params.fuse_projection_weights):
        # one GEMV instead of two, bit-identical per output column
        ug = constrain(apply_linear(params["upgate"], x, spec), "hidden")
        up, gate = jnp.split(ug, 2, axis=-1)
        return apply_linear(params["down"], jax.nn.silu(gate) * up, spec)
    if "gate" not in params:  # 2-matrix GELU variant (whisper/starcoder)
        return gelu_mlp(params, x, spec)
    up = constrain(apply_linear(params["up"], x, spec), "hidden")
    gate = constrain(apply_linear(params["gate"], x, spec), "hidden")
    return apply_linear(params["down"], jax.nn.silu(gate) * up, spec)


def init_gelu_mlp(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    """Whisper/starcoder-style 2-matrix GELU MLP."""
    ks = jax.random.split(key, 2)
    return {
        "up": init_linear(ks[0], cfg.d_model, cfg.d_ff, dtype=dtype),
        "down": init_linear(ks[1], cfg.d_ff, cfg.d_model, dtype=dtype),
    }


def gelu_mlp(params: Params, x: jax.Array, spec: LinearSpec) -> jax.Array:
    hidden = constrain(apply_linear(params["up"], x, spec), "hidden")
    return apply_linear(params["down"], jax.nn.gelu(hidden), spec)
