"""The dense decoder: the model of the configurations whose ``family`` is
``dense``.

The program's dense decoder: RMSNorm (scale ones), rotary embeddings on
half-split heads, grouped-query causal attention (windowed when the
model has a window), SwiGLU or tanh-GELU MLP, untied LM head.  This
module draws its weights from the seed (in the program's layout, with
the scales of ``chipbench/weights.py``), runs one layer of the plain
reference and counts its work; ``chipbench/bench.py`` lists what a
family module defines.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench import roofline
from chipbench.reference import HIGHEST, Q_BLOCK, qlinear, quantize_weight, \
    rmsnorm, rope
from chipbench.weights import centred, normal

NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Model:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int | None = None
    norm_eps: float = 1e-5
    mlp_variant: str = "swiglu"

    @classmethod
    def from_config(cls, model: dict) -> "Model":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in model.items() if k in names})

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads


# ---- weights, drawn from the seed ------------------------------------------


def _layer(m: Model, key):
    """One decoder layer's bf16 matmul weights."""
    out = (2 * m.n_layers) ** -0.5
    ka = jax.random.split(key, 7)
    d, hd = m.d_model, m.hd
    w = {
        "wq": normal(ka[0], d, m.n_heads * hd),
        "wk": normal(ka[1], d, m.n_kv_heads * hd),
        "wv": normal(ka[2], d, m.n_kv_heads * hd),
        "wo": normal(ka[3], m.n_heads * hd, d, out),
        "up": normal(ka[4], d, m.d_ff),
        "down": centred(normal(ka[6], m.d_ff, d, out)),
    }
    if m.mlp_variant != "gelu":
        w["gate"] = normal(ka[5], d, m.d_ff)
    return w


def _layer_keys(m: Model, seed_key):
    return jax.random.split(jax.random.split(seed_key, 3)[0], m.n_layers)


def embed_and_head(m: Model, seed_key):
    _, ke, kh = jax.random.split(seed_key, 3)
    embed = jax.random.normal(ke, (m.vocab_size, m.d_model), jnp.bfloat16)
    return embed, normal(kh, m.d_model, m.vocab_size)


@functools.partial(jax.jit, static_argnums=(0,))
def params(m: Model, seed_key):
    """The whole tree, as ``models.transformer.init_params`` lays it out
    for a dense decoder (norm scales ones, qkv biases zeros)."""
    stacked = jax.vmap(lambda k: _layer(m, k))(_layer_keys(m, seed_key))
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


@functools.partial(jax.jit, static_argnums=(0,))
def layer_weights(m: Model, seed_key, i):
    """Layer ``i``'s bf16 matmul weights, drawn as ``params`` draws them
    (the qkv biases are zeros)."""
    return _layer(m, _layer_keys(m, seed_key)[i])


# ---- the reference layer ---------------------------------------------------


def attention(m: Model, q, k, v):
    """Causal grouped-query attention of one sequence, in query blocks.
    q (S, H, hd); k, v (S, KV, hd)."""
    s, h, hd = q.shape
    rep = h // m.n_kv_heads
    qb = min(Q_BLOCK, s)
    kpos = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        qi = qi.reshape(qb, m.n_kv_heads, rep, hd)
        sc = jnp.einsum("qgrd,kgd->grqk", qi, k, precision=HIGHEST) * hd**-0.5
        qpos = i * qb + jnp.arange(qb)
        ok = kpos[None, :] <= qpos[:, None]
        if m.sliding_window:
            ok &= kpos[None, :] > qpos[:, None] - m.sliding_window
        p = jax.nn.softmax(jnp.where(ok, sc, NEG), axis=-1)
        o = jnp.einsum("grqk,kgd->qgrd", p, v, precision=HIGHEST)
        return o.reshape(qb, h * hd)

    return jax.lax.map(block, jnp.arange(s // qb)).reshape(s, h * hd)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward_layer(m: Model, x, w, bits):
    hd = m.hd
    lin = lambda a, name: qlinear(a, w[name], bits)

    def one(xs):
        s = xs.shape[0]
        pos = jnp.arange(s)
        h = rmsnorm(xs, m.norm_eps)
        q = lin(h, "wq").reshape(s, m.n_heads, hd)
        k = lin(h, "wk").reshape(s, m.n_kv_heads, hd)
        v = lin(h, "wv").reshape(s, m.n_kv_heads, hd)
        # the qkv biases are drawn as zeros: adding them changes nothing
        q, k = rope(q, pos, m.rope_theta), rope(k, pos, m.rope_theta)
        xs = xs + lin(attention(m, q, k, v), "wo")
        h = rmsnorm(xs, m.norm_eps)
        if m.mlp_variant == "gelu":
            f = jax.nn.gelu(lin(h, "up"), approximate=True)
        else:
            f = jax.nn.silu(lin(h, "gate")) * lin(h, "up")
        return xs + lin(f, "down")

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnums=(0,))
def _quantize_layer(m: Model, w):
    return {name: quantize_weight(a) for name, a in w.items()}


def layer(m: Model, x, w, bits: int):
    """One decoder layer over x (B, S, d) f32, one sequence at a time: the
    bf16 weights ``w`` of ``layer_weights`` to int4, ``bits``-bit
    activations into every linear."""
    return _forward_layer(m, x, _quantize_layer(m, w), bits)


# ---- work, counted from the shapes -----------------------------------------


def head_dim(model: dict) -> int:
    return model.get("head_dim") or model["d_model"] // model["n_heads"]


def layer_linears(model: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of every matmul weight in one decoder layer."""
    d, hd = model["d_model"], head_dim(model)
    q, kv = model["n_heads"] * hd, model["n_kv_heads"] * hd
    ff = model["d_ff"]
    out = [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d)]
    if model.get("mlp_variant", "swiglu") == "gelu":
        out += [("up", d, ff), ("down", ff, d)]
    else:
        out += [("up", d, ff), ("gate", d, ff), ("down", ff, d)]
    return out


def forward_min_time_s(model: dict, m: int, peak: dict,
                       lm_head: bool) -> float:
    """Least time of the packed linears of one forward over ``m`` rows:
    every layer's linears, plus the LM head when the call runs it."""
    shapes = layer_linears(model) * model["n_layers"]
    if lm_head:
        shapes = shapes + [("lm_head", model["d_model"], model["vocab_size"])]
    return sum(
        roofline.min_time_s(*roofline.int4_linear_cost(m, k, n),
                            peak["int8_ops"], peak["hbm_bytes_per_s"])
        for _, k, n in shapes
    )


def matmul_params(model: dict) -> tuple[int, int]:
    """(parameters of the decoder layers' matmuls, of the LM head)."""
    per_layer = sum(k * n for _, k, n in layer_linears(model))
    return per_layer * model["n_layers"], model["d_model"] * model["vocab_size"]


def attended(model: dict, pos: int) -> int:
    """Keys a query at position ``pos`` attends to (causal, windowed)."""
    w = model.get("sliding_window")
    return min(pos + 1, w) if w else pos + 1


def position_flops(model: dict, pos: int) -> int:
    """Model FLOPs of one token position through the decoder layers:
    2 per matmul parameter plus QK and PV over the attended keys."""
    layers, _ = matmul_params(model)
    attn = 4 * model["n_heads"] * head_dim(model) * attended(model, pos)
    return 2 * layers + attn * model["n_layers"]


def prompt_flops(model: dict, length: int) -> int:
    """FLOPs of prefilling ``length`` prompt positions (no LM head)."""
    layers, _ = matmul_params(model)
    w = model.get("sliding_window") or length
    att = sum(min(p + 1, w) for p in range(length))
    return (2 * layers * length
            + 4 * model["n_heads"] * head_dim(model) * att * model["n_layers"])


def lm_head_flops(model: dict) -> int:
    return 2 * matmul_params(model)[1]
