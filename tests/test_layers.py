"""Layer-level unit tests: rmsnorm, rope, GQA attention, KV caches, SWA."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig
from repro.models import layers as L

CFG = ModelConfig(
    name="t", family="dense", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
    d_ff=64, vocab_size=64, dtype="float32",
)


def test_rmsnorm_unit_scale():
    p = L.init_rmsnorm(16)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 16)) * 5
    y = L.rmsnorm(p, x)
    rms = jnp.sqrt(jnp.mean(y * y, axis=-1))
    np.testing.assert_allclose(np.asarray(rms), 1.0, atol=1e-3)


def test_rope_preserves_norm_and_relative_positions():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
    pos = jnp.arange(8)[None]
    y = L.rope(x, pos, 10_000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(y), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    # dot(q_i, k_j) depends only on i - j
    q = jnp.ones((1, 8, 1, 16))
    k = jnp.ones((1, 8, 1, 16))
    qr, kr = L.rope(q, pos, 1e4), L.rope(k, pos, 1e4)
    d1 = jnp.einsum("bshd,bthd->st", qr, kr)
    assert abs(d1[3, 1] - d1[5, 3]) < 1e-4


def test_attention_causality():
    key = jax.random.PRNGKey(0)
    p = L.init_attention(key, CFG)
    x = jax.random.normal(key, (1, 8, 32))
    pos = jnp.arange(8)[None]
    out1, _ = L.attention(p, x, CFG, pos)
    x2 = x.at[:, -1].set(99.0)  # future token change must not leak backward
    out2, _ = L.attention(p, x2, CFG, pos)
    np.testing.assert_allclose(
        np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), atol=1e-5
    )


def test_decode_matches_full_forward():
    key = jax.random.PRNGKey(2)
    p = L.init_attention(key, CFG)
    x = jax.random.normal(key, (2, 6, 32))
    pos = jnp.arange(6)[None]
    full, _ = L.attention(p, x, CFG, pos)
    cache = L.init_kv_cache(CFG, 2, 8, dtype=jnp.float32)
    outs = []
    for t in range(6):
        o, cache = L.attention(
            p, x[:, t : t + 1], CFG, jnp.full((2, 1), t), cache=cache
        )
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec), atol=1e-4)


def test_swa_ring_buffer_decode():
    cfg = dataclasses.replace(CFG, sliding_window=4)
    key = jax.random.PRNGKey(3)
    p = L.init_attention(key, cfg)
    cache = L.init_kv_cache(cfg, 1, 16, dtype=jnp.float32)
    assert cache["k"].shape[1] == 4  # ring buffer is window-sized
    x = jax.random.normal(key, (1, 10, 32))
    out = None
    for t in range(10):
        out, cache = L.attention(
            p, x[:, t : t + 1], cfg, jnp.full((1, 1), t), cache=cache
        )
    assert np.isfinite(np.asarray(out)).all()

    # reference: full attention with window mask over the last 4 tokens
    full, _ = L.attention(p, x, cfg, jnp.arange(10)[None])
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(full[:, -1]), atol=1e-4
    )


def test_gqa_head_broadcast():
    x = jnp.ones((1, 2, 2, 4))
    out = L._repeat_kv(x, 3)
    assert out.shape == (1, 2, 6, 4)
    np.testing.assert_array_equal(np.asarray(out[:, :, 0]), np.asarray(out[:, :, 2]))


def _repeat_then_attend(q, k, v, valid, dtype):
    """The repeat-then-attend form grouped-query ``_scores`` replaces:
    every KV head copied n_heads / n_kv_heads times, then plain MHA."""
    r = q.shape[2] // k.shape[2]
    k, v = L._repeat_kv(k, r), L._repeat_kv(v, r)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
              * q.shape[-1]**-0.5)
    if valid is not None:
        scores = scores + jnp.where(valid[:, None, :, :], 0.0, L.NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("masked", [True, False])
@pytest.mark.parametrize("s", [1, 4])
@pytest.mark.parametrize("nh,nkv", [(4, 4), (8, 2), (6, 3), (9, 1)])
def test_grouped_scores_match_repeat_then_attend(nh, nkv, s, masked, dtype):
    dtype = jnp.dtype(dtype)
    b, t, hd = 3, 10, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(nh * 10 + nkv), 3)
    q = jax.random.normal(kq, (b, s, nh, hd)).astype(dtype)
    k = jax.random.normal(kk, (b, t, nkv, hd)).astype(dtype)
    v = jax.random.normal(kv, (b, t, nkv, hd)).astype(dtype)
    # per-row validity: each row sits at its own depth, causal within s
    depth = jnp.array([2, 5, t - s])
    valid = (jnp.arange(t)[None, None, :]
             <= depth[:, None, None] + jnp.arange(s)[None, :, None])
    valid = valid if masked else None
    got = L._scores(q, k, v, valid, dtype, decode=True)
    ref = _repeat_then_attend(q, k, v, valid, dtype)
    assert got.shape == ref.shape == (b, s, nh, hd) and got.dtype == dtype
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, ref, rtol=0, atol=1e-5)
    else:
        # within 2 bf16 ulp of the reference value (8 significand bits)
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(ref), 2.0**-126))) - 7)
        assert np.all(np.abs(got - ref) <= 2 * ulp)


def _jaxpr_shapes(jaxpr):
    """Every intermediate's shape, nested jaxprs (pjit, scans) included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield tuple(var.aval.shape)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _jaxpr_shapes(inner)


@pytest.mark.parametrize("s", [1, 4])
def test_paged_gqa_attention_never_repeats_kv(s):
    """GQA paged attention gathers each lane's KV window at n_kv_heads and
    never writes it out again at n_heads."""
    cfg = CFG  # 4 query heads over 2 KV heads
    b, ps, max_blocks = 2, 4, 3
    window, nh, nkv, hd = max_blocks * ps, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = L.init_attention(jax.random.PRNGKey(5), cfg)
    cache = L.init_paged_kv_cache(cfg, n_pages=b * max_blocks, page_size=ps,
                                  dtype=jnp.float32)
    page_table = jnp.arange(b * max_blocks, dtype=jnp.int32).reshape(b, max_blocks)
    x = jnp.ones((b, s, cfg.d_model))
    pos = jnp.array([[3], [5]]) + jnp.arange(s)[None]
    jaxpr = jax.make_jaxpr(
        lambda p, x, pos, c, pt: L.attention(p, x, cfg, pos, cache=c, page_table=pt)
    )(p, x, pos, cache, page_table).jaxpr
    shapes = set(_jaxpr_shapes(jaxpr))
    assert (b, window, nkv, hd) in shapes  # the gather is seen
    assert (b, window, nh, hd) not in shapes
    assert (b, window, nkv, nh // nkv, hd) not in shapes


def test_cached_prefill_multitoken():
    """Full-attention cached prefill (s>1) matches uncached forward."""
    key = jax.random.PRNGKey(4)
    p = L.init_attention(key, CFG)
    x = jax.random.normal(key, (1, 6, 32))
    pos = jnp.arange(6)[None]
    full, _ = L.attention(p, x, CFG, pos)
    cache = L.init_kv_cache(CFG, 1, 8, dtype=jnp.float32)
    got, cache2 = L.attention(p, x, CFG, pos, cache=cache)
    np.testing.assert_allclose(np.asarray(full), np.asarray(got), atol=1e-4)
    assert cache2 is not None


def test_chunked_attention_matches_naive():
    cfg = dataclasses.replace(CFG, attention_chunk=16)
    key = jax.random.PRNGKey(7)
    p = L.init_attention(key, CFG)
    x = jax.random.normal(key, (2, 64, 32))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (2, 64))
    ref, _ = L.attention(p, x, CFG, pos)
    got, _ = L.attention(p, x, cfg, pos)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=2e-5)


def test_chunked_attention_swa_matches_naive():
    base = dataclasses.replace(CFG, sliding_window=24)
    cfg = dataclasses.replace(base, attention_chunk=16)
    key = jax.random.PRNGKey(8)
    p = L.init_attention(key, base)
    x = jax.random.normal(key, (1, 64, 32))
    pos = jnp.broadcast_to(jnp.arange(64)[None], (1, 64))
    ref, _ = L.attention(p, x, base, pos)
    got, _ = L.attention(p, x, cfg, pos)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=2e-5)
