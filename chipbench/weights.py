"""How the benchmark draws a served model's weights from the seed.

Each family module (``families/<family>.py``) makes its whole tree on the
device in one jitted call, in bf16 (the type it is served in), in the
layout the program takes, from these two draws; its reference draws the
same layer again alone.

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

import jax
import jax.numpy as jnp


def normal(key, d_in, d_out, scale=1.0):
    """A (d_in, d_out) bf16 projection, N(0, scale² / d_in)."""
    return jax.random.normal(key, (d_in, d_out), jnp.bfloat16) * (
        d_in**-0.5 * scale)


def centred(w):
    """``w`` less its mean over its inputs, column by column."""
    w = w.astype(jnp.float32)
    return (w - jnp.mean(w, axis=0, keepdims=True)).astype(jnp.bfloat16)
