"""Unified LM covering all assigned families (dense / moe / ssm / hybrid /
encdec / vlm).

Layers are organized into *scan groups*: every group has identical pytree
structure, group params are stacked along a leading ``n_groups`` axis, and
the forward pass is one ``lax.scan`` over that axis.  This keeps the lowered
HLO size O(1) in depth — essential for compiling 80-layer models on the
1-core dry-run host — and gives the remat boundary (one group).

Family → group structure:
  dense   1 layer:   attn + SwiGLU (or GELU) MLP
  moe     1 layer:   attn + top-k MoE FFN
  ssm     ``slstm_every`` layers: 1 sLSTM + (g-1) mLSTM blocks (no outer FFN)
  hybrid  ``attn_every`` layers (jamba): attention at the middle slot, Mamba
          elsewhere; MoE FFN on odd slots, dense MLP on even slots
  encdec  decoder group: self-attn + cross-attn + GELU MLP (encoder separate)
  vlm     dense backbone; stub patch embeddings are prepended to the sequence
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from ..core.packed_linear import apply_linear, init_linear
from ..runtime.act_sharding import constrain, constrain_group_params
from .config import ModelConfig
from .layers import (
    Params,
    attention,
    gelu_mlp,
    init_attention,
    init_gelu_mlp,
    init_kv_cache,
    init_mlp,
    init_paged_kv_cache,
    init_rmsnorm,
    mlp,
    rmsnorm,
)
from .moe import init_moe, moe_ffn
from .ssm import (
    init_mamba,
    init_mamba_cache,
    init_mlstm,
    init_mlstm_cache,
    init_slstm,
    init_slstm_cache,
    mamba,
    mlstm,
    slstm,
)

__all__ = [
    "init_params", "forward", "encode", "init_cache", "init_paged_cache",
    "Model",
]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_group(key, cfg: ModelConfig, dtype, cross_attn: bool = False) -> Params:
    fam = cfg.family
    d = cfg.d_model
    if fam in ("dense", "vlm") or (fam == "encdec" and not cross_attn):
        ks = jax.random.split(key, 2)
        make_mlp = (
            init_gelu_mlp
            if (fam == "encdec" or cfg.mlp_variant == "gelu")
            else init_mlp
        )
        return {
            "ln1": init_rmsnorm(d, dtype),
            "attn": init_attention(ks[0], cfg, dtype),
            "ln2": init_rmsnorm(d, dtype),
            "mlp": make_mlp(ks[1], cfg, dtype),
        }
    if fam == "encdec":  # decoder group
        ks = jax.random.split(key, 3)
        return {
            "ln1": init_rmsnorm(d, dtype),
            "attn": init_attention(ks[0], cfg, dtype),
            "ln_x": init_rmsnorm(d, dtype),
            "xattn": init_attention(ks[1], cfg, dtype),
            "ln2": init_rmsnorm(d, dtype),
            "mlp": init_gelu_mlp(ks[2], cfg, dtype),
        }
    if fam == "moe":
        ks = jax.random.split(key, 2)
        return {
            "ln1": init_rmsnorm(d, dtype),
            "attn": init_attention(ks[0], cfg, dtype),
            "ln2": init_rmsnorm(d, dtype),
            "moe": init_moe(ks[1], cfg, dtype),
        }
    if fam == "ssm":
        g = cfg.group_size
        ks = jax.random.split(key, g)
        ml = [init_mlstm(k, cfg, dtype) for k in ks[1:]]
        return {
            "ln_s": init_rmsnorm(d, dtype),
            "slstm": init_slstm(ks[0], cfg, dtype),
            "ln_m": {"scale": jnp.ones((g - 1, d), dtype)},
            "mlstm": jax.tree.map(lambda *xs: jnp.stack(xs), *ml),
        }
    if fam == "hybrid":
        g = cfg.attn_every
        ks = jax.random.split(key, 2 * g + 2)
        n_mamba = g - 1
        n_moe = g // 2
        n_mlp = g - n_moe
        mam = [init_mamba(ks[i], cfg, dtype) for i in range(n_mamba)]
        moes = [init_moe(ks[n_mamba + i], cfg, dtype) for i in range(n_moe)]
        mlps = [init_mlp(ks[n_mamba + n_moe + i], cfg, dtype) for i in range(n_mlp)]
        stack = lambda xs: jax.tree.map(lambda *t: jnp.stack(t), *xs)
        return {
            "ln_mix": {"scale": jnp.ones((g, d), dtype)},
            "ln_ffn": {"scale": jnp.ones((g, d), dtype)},
            "attn": init_attention(ks[-1], cfg, dtype),
            "mamba": stack(mam),
            "moe": stack(moes),
            "mlp": stack(mlps),
        }
    raise ValueError(f"unknown family {fam}")


def init_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, 6)
    d, v = cfg.d_model, cfg.vocab_size
    group_keys = jax.random.split(keys[0], cfg.n_groups)
    groups = jax.vmap(
        lambda k: _init_group(k, cfg, dtype, cross_attn=cfg.family == "encdec")
    )(group_keys)
    params: Params = {
        "embed": {"w": jax.random.normal(keys[1], (v, d), dtype) * 0.02},
        "groups": groups,
        "final_norm": init_rmsnorm(d, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_linear(keys[2], d, v, dtype=dtype)
    if cfg.family == "encdec":
        enc_cfg = cfg  # same width; encoder groups are plain attn+mlp
        ekeys = jax.random.split(keys[3], cfg.n_encoder_layers)
        params["encoder"] = {
            "groups": jax.vmap(
                lambda k: _init_group(k, enc_cfg, dtype, cross_attn=False)
            )(ekeys),
            "final_norm": init_rmsnorm(d, dtype),
        }
    if cfg.family == "vlm":
        params["patch_proj"] = init_linear(keys[4], d, d, dtype=dtype)
    return params


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    """Stacked (n_groups, ...) decode cache matching the scan layout.

    ``dtype=None`` derives the KV dtype from the model's compute dtype, so a
    float32 model gets a float32 cache (bit-exact cached decode) while bf16
    models keep the bandwidth-saving bf16 cache.
    """
    if dtype is None:
        dtype = _dtype(cfg)

    def one_group():
        fam = cfg.family
        if fam in ("dense", "vlm", "moe"):
            return {"attn": init_kv_cache(cfg, batch, max_len, dtype)}
        if fam == "encdec":
            return {"attn": init_kv_cache(cfg, batch, max_len, dtype)}
        if fam == "ssm":
            g = cfg.group_size
            ml = [init_mlstm_cache(cfg, batch, jnp.float32) for _ in range(g - 1)]
            return {
                "slstm": init_slstm_cache(cfg, batch, jnp.float32),
                "mlstm": jax.tree.map(lambda *xs: jnp.stack(xs), *ml),
            }
        if fam == "hybrid":
            g = cfg.attn_every
            mam = [init_mamba_cache(cfg, batch, jnp.float32) for _ in range(g - 1)]
            return {
                "attn": init_kv_cache(cfg, batch, max_len, dtype),
                "mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *mam),
            }
        raise ValueError(fam)

    one = one_group()
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_groups,) + x.shape).copy(), one
    )


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int,
                     dtype=None, batch: int | None = None):
    """Stacked (n_groups, ...) paged decode cache: per-layer physical page
    pools written/read through a per-lane page table (the continuous
    serving engine's cache — see ``serving.paged_cache``).

    Full-attention KV pages through the table.  Recurrent state
    (ssm / hybrid mamba) has no positional layout to page — it is O(1) per
    lane — so it rides along as ordinary per-lane state arrays with a
    ``batch`` (= n_lanes) leading axis, exactly the ``init_cache`` layout;
    hybrid caches mix both kinds of leaf in one tree.  Sliding-window KV
    pages the ring buffer itself: page tables address ring slots
    ``pos % window`` rather than absolute positions, so the pool per lane
    is bounded by the window.
    """
    if dtype is None:
        dtype = _dtype(cfg)
    fam = cfg.family
    if fam in ("ssm", "hybrid") and batch is None:
        raise ValueError(
            f"family {fam!r} keeps per-lane recurrent state in its paged "
            "cache: pass batch=<n_lanes>"
        )
    if fam == "ssm":
        # no KV anywhere: the "paged" cache is pure per-lane state
        return init_cache(cfg, batch, 1, dtype)
    if fam == "hybrid":
        g = cfg.attn_every
        mam = [init_mamba_cache(cfg, batch, jnp.float32) for _ in range(g - 1)]
        one = {
            "attn": init_paged_kv_cache(cfg, n_pages, page_size, dtype),
            "mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *mam),
        }
    else:
        one = {"attn": init_paged_kv_cache(cfg, n_pages, page_size, dtype)}
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_groups,) + x.shape).copy(), one
    )


# ---------------------------------------------------------------------------
# group apply
# ---------------------------------------------------------------------------


def _apply_group(
    gp: Params,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    cache: Params | None,
    encoder_out: jax.Array | None,
    causal: bool = True,
    page_table: jax.Array | None = None,
    valid: jax.Array | None = None,
) -> tuple[jax.Array, Params | None, jax.Array]:
    """Returns (x, new_cache, aux_loss).

    ``valid`` (B, S) bool is the serving engines' per-row prefix mask:
    recurrent mixers gate their carried state on it (so a chunked prefill
    advances each lane's state by exactly its valid tokens — see
    ``models.ssm``) and MoE dispatch drops invalid tokens from the
    capacity competition.  ``None`` (training / full-batch eval) keeps the
    chunked/batched fast paths.
    """
    fam = cfg.family
    spec = cfg.quant
    aux = jnp.zeros((), jnp.float32)
    # every residual join is pinned to the (dp, None, None) layout in compute
    # dtype so deferred row-parallel psums/gathers move bf16, not f32
    # (EXPERIMENTS.md §Perf iteration 2)
    add = lambda a, b: constrain(a + b, "residual")

    if fam in ("dense", "vlm") or (fam == "encdec" and encoder_out is None and cache is None and not causal):
        h, new_kv = attention(
            gp["attn"], rmsnorm(gp["ln1"], x, cfg.norm_eps), cfg, positions,
            cache=None if cache is None else cache["attn"], causal=causal,
            page_table=page_table,
        )
        x = add(x, h)
        h = rmsnorm(gp["ln2"], x, cfg.norm_eps)
        with jax.named_scope("mlp"):
            y = mlp(gp["mlp"], h, spec)
        x = add(x, y)
        return x, None if new_kv is None else {"attn": new_kv}, aux

    if fam == "moe":
        h, new_kv = attention(
            gp["attn"], rmsnorm(gp["ln1"], x, cfg.norm_eps), cfg, positions,
            cache=None if cache is None else cache["attn"],
            page_table=page_table,
        )
        x = add(x, h)
        y, aux = moe_ffn(
            gp["moe"], rmsnorm(gp["ln2"], x, cfg.norm_eps), cfg, spec,
            valid=valid,
        )
        return add(x, y), None if new_kv is None else {"attn": new_kv}, aux

    if fam == "encdec":  # decoder group
        h, new_kv = attention(
            gp["attn"], rmsnorm(gp["ln1"], x, cfg.norm_eps), cfg, positions,
            cache=None if cache is None else cache["attn"],
            page_table=page_table,
        )
        x = add(x, h)
        h, _ = attention(
            gp["xattn"], rmsnorm(gp["ln_x"], x, cfg.norm_eps), cfg, positions,
            causal=False, kv_x=encoder_out,
        )
        x = add(x, h)
        h = rmsnorm(gp["ln2"], x, cfg.norm_eps)
        with jax.named_scope("mlp"):
            y = gelu_mlp(gp["mlp"], h, spec)
        x = add(x, y)
        return x, None if new_kv is None else {"attn": new_kv}, aux

    if fam == "ssm":
        g = cfg.group_size
        h, new_s = slstm(
            gp["slstm"], rmsnorm(gp["ln_s"], x, cfg.norm_eps), cfg,
            cache=None if cache is None else cache["slstm"], valid=valid,
        )
        x = add(x, h)
        new_ml = []
        for i in range(g - 1):
            sub = jax.tree.map(lambda t: t[i], gp["mlstm"])
            c_i = None if cache is None else jax.tree.map(lambda t: t[i], cache["mlstm"])
            h, nc = mlstm(
                sub, rmsnorm({"scale": gp["ln_m"]["scale"][i]}, x, cfg.norm_eps),
                cfg, cache=c_i, valid=valid,
            )
            x = add(x, h)
            new_ml.append(nc)
        new_cache = None
        if cache is not None:
            new_cache = {
                "slstm": new_s,
                "mlstm": jax.tree.map(lambda *t: jnp.stack(t), *new_ml),
            }
        return x, new_cache, aux

    if fam == "hybrid":
        g = cfg.attn_every
        attn_slot = g // 2
        mamba_i = moe_i = mlp_i = 0
        new_mam = []
        new_kv = None
        for slot in range(g):
            ln_mix = {"scale": gp["ln_mix"]["scale"][slot]}
            ln_ffn = {"scale": gp["ln_ffn"]["scale"][slot]}
            if slot == attn_slot:
                h, new_kv = attention(
                    gp["attn"], rmsnorm(ln_mix, x, cfg.norm_eps), cfg, positions,
                    cache=None if cache is None else cache["attn"],
                    page_table=page_table,
                )
                x = add(x, h)
            else:
                sub = jax.tree.map(lambda t: t[mamba_i], gp["mamba"])
                c_i = (
                    None
                    if cache is None
                    else jax.tree.map(lambda t: t[mamba_i], cache["mamba"])
                )
                h, nc = mamba(
                    sub, rmsnorm(ln_mix, x, cfg.norm_eps), cfg, cache=c_i,
                    valid=valid,
                )
                x = add(x, h)
                new_mam.append(nc)
                mamba_i += 1
            if slot % 2 == 1 and cfg.n_experts:
                sub = jax.tree.map(lambda t: t[moe_i], gp["moe"])
                y, a = moe_ffn(
                    sub, rmsnorm(ln_ffn, x, cfg.norm_eps), cfg, spec,
                    valid=valid,
                )
                x = add(x, y)
                aux = aux + a
                moe_i += 1
            else:
                sub = jax.tree.map(lambda t: t[mlp_i], gp["mlp"])
                h = rmsnorm(ln_ffn, x, cfg.norm_eps)
                with jax.named_scope("mlp"):
                    y = mlp(sub, h, spec)
                x = add(x, y)
                mlp_i += 1
        new_cache = None
        if cache is not None:
            new_cache = {
                "attn": new_kv,
                "mamba": jax.tree.map(lambda *t: jnp.stack(t), *new_mam),
            }
        return x, new_cache, aux

    raise ValueError(fam)


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)  # "full": save nothing


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _scan_groups(
    groups, x, cfg, positions, cache, encoder_out, causal=True,
    page_table=None, valid=None,
):
    def body(carry, xs):
        gp, cache_g = xs
        gp = constrain_group_params(gp)
        y, new_c, aux = _apply_group(
            gp, constrain(carry, "residual"), cfg, positions, cache_g,
            encoder_out, causal, page_table=page_table, valid=valid,
        )
        return constrain(y, "residual"), (new_c, aux)

    body = _remat(body, cfg.remat)
    if cfg.scan_layers:
        x, (new_cache, auxes) = jax.lax.scan(body, x, (groups, cache))
        return x, new_cache, jnp.sum(auxes)
    n = jax.tree.leaves(groups)[0].shape[0]
    new_cs, aux_t = [], 0.0
    for i in range(n):
        gp = jax.tree.map(lambda t: t[i], groups)
        cg = None if cache is None else jax.tree.map(lambda t: t[i], cache)
        x, (nc, aux) = body(x, (gp, cg))
        new_cs.append(nc)
        aux_t = aux_t + aux
    new_cache = (
        None
        if cache is None
        else jax.tree.map(lambda *t: jnp.stack(t), *new_cs)
    )
    return x, new_cache, aux_t


def _sinusoidal(length: int, d: int) -> jax.Array:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None]
    angle = pos / jnp.power(10_000.0, dim / d)
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


def encode(params: Params, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """Whisper encoder over stub frame embeddings (B, T, d)."""
    frames = frames.astype(_dtype(cfg))
    x = frames + _sinusoidal(frames.shape[1], cfg.d_model)[None].astype(frames.dtype)
    positions = jnp.arange(frames.shape[1])[None]

    # encoder groups are plain bidirectional attn+mlp; reuse dense group path
    enc_cfg = cfg
    x, _, _ = _scan_groups(
        params["encoder"]["groups"], x, enc_cfg, positions, None, None, causal=False
    )
    return rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps)


def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,
    positions: jax.Array | None = None,
    cache: Params | None = None,
    encoder_out: jax.Array | None = None,
    patch_embeds: jax.Array | None = None,
    logits_dtype=jnp.float32,
    return_hidden: bool = False,
    page_table: jax.Array | None = None,
    valid: jax.Array | None = None,
) -> tuple[jax.Array, Params | None, jax.Array]:
    """Token ids → logits.  Returns (logits, new_cache, aux_loss).

    decode: ``tokens`` is (B, 1) and ``cache`` holds the stacked KV/state.
    vlm: ``patch_embeds`` (B, P, d) is prepended to the embedded tokens.
    ``return_hidden`` skips the lm_head and returns the post-final-norm
    hidden states instead of logits — serving prefill projects only the
    last prompt position, not every position of every chunk.
    ``page_table`` (B, max_blocks) routes KV writes/reads through a paged
    cache (``init_paged_cache``) instead of per-lane dense windows.
    ``valid`` (B, S) bool marks which token slots are real (serving
    engines' per-row prefix mask): recurrent state advances only on valid
    tokens and MoE capacity ignores invalid ones.
    """
    x = params["embed"]["w"][tokens].astype(_dtype(cfg))
    if patch_embeds is not None:
        pe = apply_linear(params["patch_proj"], patch_embeds.astype(x.dtype), cfg.quant)
        x = jnp.concatenate([pe, x], axis=1)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    x, new_cache, aux = _scan_groups(
        params["groups"], x, cfg, positions, cache, encoder_out,
        page_table=page_table, valid=valid,
    )
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return x, new_cache, aux
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            logits = (x.astype(logits_dtype)
                      @ params["embed"]["w"].T.astype(logits_dtype))
        else:
            logits = apply_linear(
                params["lm_head"], x, cfg.quant
            ).astype(logits_dtype)
    return logits, new_cache, aux


def _dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


class Model:
    """Thin OO veneer over the functional API (used by examples/serving)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def init(self, key, dtype=jnp.float32) -> Params:
        return init_params(key, self.cfg, dtype)

    def init_cache(self, batch: int, max_len: int, dtype=None):
        return init_cache(self.cfg, batch, max_len, dtype)

    def __call__(self, params, tokens, **kw):
        return forward(params, self.cfg, tokens, **kw)
