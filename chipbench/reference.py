"""Plain reference of the served models, and the check that reads it.

Written from the model's equations in ``jax.numpy`` and float32 (every
float matmul at ``Precision.HIGHEST``), with no cache, kernel or batching
of the program.  It imports nothing of the program and takes nothing the
program made: it draws the weights anew from the seed, layer by layer,
with the configuration's family module (``families/<family>.py``, which
also made the tree the program serves), and quantizes them as the
configuration states: weights to int4 per output channel (absmax / 7),
activations to int8 per row (absmax / 127) before every linear, with
exact integer products.

``gaps`` runs the reference over each sampled request's prompt and served
tokens and returns, for every served token, how far that token's logit
lies below the reference's best at its position.  At a precision of
``CONTROLS`` the same forward is a control: the reference at the next
precision below the int8 activations the configuration states, int4
activations into every linear, whose own first choices are read against
the float32 reference the same way.

What is here is the same for every family: the quantized linear, the
norm and rotary embeddings a family's layer may use, and the loop
embedding -> the family's layers -> final RMSNorm (scale ones) and untied
LM head.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# activation bits into every linear
REFERENCE = 8
CONTROLS = {"int4": 4}   # the step below the stated int8 activations
Q_BLOCK = 512     # every sequence is padded to a multiple of this
ROW_BLOCK = 512   # LM-head rows per block


def quantize_weight(w):
    """int4 per output channel: (int8 values in [-8, 7], f32 scale)."""
    w = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-8) / 7
    return jnp.clip(jnp.round(w / scale), -8, 7).astype(jnp.int8), scale


# ---- forward ---------------------------------------------------------------


def qlinear(x, w, act_bits: int):
    """x (R, K) f32 through activations quantized per row to ``act_bits``
    and the int4 weight ``w`` (``quantize_weight``'s pair), with exact
    integer products."""
    values, w_scale = w
    qmax = (1 << (act_bits - 1)) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / qmax
    xq = jnp.clip(jnp.round(x / s), -qmax - 1, qmax).astype(jnp.int8)
    acc = jax.lax.dot(xq, values, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * s * w_scale


def rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope(x, pos, theta):
    """x (S, H, hd), pos (S,): rotation of the two halves of each head."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnums=(0, 6))
def _head_gaps(m, x_ref, head, x_ctl, rows, toks, ctl=None):
    """Per served token: the float32 reference's best logit minus its logit
    at the served token, its best minus its second best, and (with
    ``x_ctl``, the final hidden states with ``ctl``-bit activations) its
    best minus its logit at the control's own first choice.  ``rows``
    index the flattened (B*S) positions; padded rows are -1."""

    def block(args):
        r, t = args
        valid = r >= 0
        r = jnp.maximum(r, 0)
        lr = qlinear(rmsnorm(x_ref[r], m.norm_eps), head, REFERENCE)
        top2 = jax.lax.top_k(lr, 2)[0]
        gap = top2[:, 0] - jnp.take_along_axis(lr, t[:, None], 1)[:, 0]
        out = [gap, top2[:, 0] - top2[:, 1]]
        if x_ctl is not None:
            lc = qlinear(rmsnorm(x_ctl[r], m.norm_eps), head, ctl)
            pick = jnp.argmax(lc, axis=-1)
            out.append(top2[:, 0]
                       - jnp.take_along_axis(lr, pick[:, None], 1)[:, 0])
        return jnp.where(valid, jnp.stack(out), 0.0)

    nb = rows.shape[0] // ROW_BLOCK
    res = jax.lax.map(block, (rows.reshape(nb, ROW_BLOCK),
                              toks.reshape(nb, ROW_BLOCK)))
    return res.transpose(1, 0, 2).reshape(res.shape[1], -1)


def _pad_to(n: int, mult: int) -> int:
    return max(mult, -(-n // mult) * mult)


def gaps(family, model: dict, weight_seed: int, seqs, n_rows: int,
         seq_len: int, control: str | None = None) -> dict:
    """Read the served tokens of ``seqs`` — (prompt, served tokens) pairs,
    at most ``n_rows`` of them — against the reference of ``family`` (a
    family module) at the configuration's ``model``.

    ``seq_len`` pads every sequence to one fixed length (the mix's longest
    request, rounded up to ``Q_BLOCK``), so the programs compile once per
    cell.  Returns numpy arrays of per-token gaps (``gap``;
    ``control_gap`` with ``control``, a name of ``CONTROLS``) and the
    reference's top-two margins (``margin``)."""
    m = family.Model.from_config(model)
    s_pad = _pad_to(seq_len, Q_BLOCK)
    b = n_rows
    tokens = np.zeros((b, s_pad), np.int32)
    rows, toks = [], []
    for i, (prompt, served) in enumerate(seqs):
        full = list(prompt) + list(served)
        tokens[i, :len(full)] = full
        for j, t in enumerate(served):
            rows.append(i * s_pad + len(prompt) - 1 + j)
            toks.append(t)
    n_tok = len(rows)
    r_pad = _pad_to(n_tok, ROW_BLOCK)
    rows_a = np.full(r_pad, -1, np.int32)
    rows_a[:n_tok] = rows
    toks_a = np.zeros(r_pad, np.int32)
    toks_a[:n_tok] = toks

    key = jax.random.PRNGKey(weight_seed)
    embed, head = jax.jit(family.embed_and_head, static_argnums=0)(m, key)
    x = embed[jnp.asarray(tokens)].astype(jnp.float32)
    del embed
    ctl = CONTROLS[control] if control else None
    xc = x if ctl else None
    for i in range(m.n_layers):
        w = family.layer_weights(m, key, i)
        x = family.layer(m, x, w, REFERENCE)
        if ctl:
            xc = family.layer(m, xc, w, ctl)
        del w
    flat = x.reshape(b * s_pad, -1)
    flat_c = None if xc is None else xc.reshape(b * s_pad, -1)
    head_q = jax.jit(quantize_weight)(head)
    out = np.asarray(_head_gaps(m, flat, head_q, flat_c, jnp.asarray(rows_a),
                                jnp.asarray(toks_a), ctl))
    res = {"gap": out[0, :n_tok], "margin": out[1, :n_tok]}
    if ctl:
        res["control_gap"] = out[2, :n_tok]
    return res
