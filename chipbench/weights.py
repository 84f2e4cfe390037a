"""The served model's weights, drawn from the seed by the benchmark.

One jitted call makes the whole tree on the device, in bf16 (the type it
is served in), in the layout the program's dense decoder takes; the
reference draws the same layer again with ``layer`` alone.

The scales keep the token in charge of the residual stream: the
embedding is N(0, 1), every projection N(0, 1/fan_in), and the two that
write into the residual stream (attention output, MLP down) are further
scaled by (2 · n_layers)^-1/2; each column of the MLP down projection is
centred (sums to zero over its inputs), so the positive mean of GELU or
SiLU adds no vector that is the same at every position.  With the
program's own initialisation (embedding 0.02, unscaled outputs), and
without the centring at a head size of 128, random models answer every
prompt with one token repeated, by a wide margin, so no lower precision
would change a served token and the comparison with the reference could
tell nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _normal(key, d_in, d_out, scale=1.0):
    return jax.random.normal(key, (d_in, d_out), jnp.bfloat16) * (
        d_in**-0.5 * scale)


def _centred(w):
    w = w.astype(jnp.float32)
    return (w - jnp.mean(w, axis=0, keepdims=True)).astype(jnp.bfloat16)


def layer(m, key):
    """One decoder layer's bf16 matmul weights, ``m`` a reference.Model."""
    out = (2 * m.n_layers) ** -0.5
    ka = jax.random.split(key, 7)
    d, hd = m.d_model, m.hd
    w = {
        "wq": _normal(ka[0], d, m.n_heads * hd),
        "wk": _normal(ka[1], d, m.n_kv_heads * hd),
        "wv": _normal(ka[2], d, m.n_kv_heads * hd),
        "wo": _normal(ka[3], m.n_heads * hd, d, out),
        "up": _normal(ka[4], d, m.d_ff),
        "down": _centred(_normal(ka[6], m.d_ff, d, out)),
    }
    if m.mlp_variant != "gelu":
        w["gate"] = _normal(ka[5], d, m.d_ff)
    return w


def layer_keys(m, seed_key):
    return jax.random.split(jax.random.split(seed_key, 3)[0], m.n_layers)


def embed_and_head(m, seed_key):
    _, ke, kh = jax.random.split(seed_key, 3)
    embed = jax.random.normal(ke, (m.vocab_size, m.d_model), jnp.bfloat16)
    return embed, _normal(kh, m.d_model, m.vocab_size)


@functools.partial(jax.jit, static_argnums=(0,))
def params(m, seed_key):
    """The whole tree, as ``models.transformer.init_params`` lays it out
    for a dense decoder (norm scales ones, qkv biases zeros)."""
    stacked = jax.vmap(lambda k: layer(m, k))(layer_keys(m, seed_key))
    n, d = m.n_layers, m.d_model
    lin = lambda w: {"w": w}
    attn = {k: lin(stacked[k]) for k in ("wq", "wk", "wv", "wo")}
    if m.qkv_bias:
        for k in ("wq", "wk", "wv"):
            attn[k]["b"] = jnp.zeros((n, stacked[k].shape[-1]), jnp.bfloat16)
    mlp_keys = ("up", "down") if m.mlp_variant == "gelu" else \
        ("up", "gate", "down")
    ones = jnp.ones((n, d), jnp.bfloat16)
    embed, head = embed_and_head(m, seed_key)
    return {
        "embed": {"w": embed},
        "groups": {"ln1": {"scale": ones}, "attn": attn,
                   "ln2": {"scale": ones},
                   "mlp": {k: lin(stacked[k]) for k in mlp_keys}},
        "final_norm": {"scale": jnp.ones((d,), jnp.bfloat16)},
        "lm_head": {"w": head},
    }
