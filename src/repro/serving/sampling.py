"""Token sampling for the serving engine.

One jit-friendly primitive, ``sample_tokens``, drives both the prefill
first-token draw and every decode step: temperature, top-k and top-p are
per-slot *arrays* so a single batched call serves heterogeneous requests
(one slot greedy, the neighbour at temperature 0.9/top-p 0.95).

Randomness is stateless: each slot gets a base PRNG key derived from its
request id (``slot_key``), and every step folds in the slot's current
position — the (request, position) pair fully determines the draw, so a
replayed request reproduces its tokens bit-for-bit regardless of what the
other slots were doing.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["SamplingParams", "GREEDY", "slot_key", "sample_tokens"]

NEG_INF = -1e30  # mask value; dominates any temperature-scaled logit


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling controls.  ``temperature == 0`` means greedy;
    ``top_k == 0`` and ``top_p == 1.0`` disable the respective filters."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0

    def __post_init__(self) -> None:
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0 < self.top_p <= 1:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")


GREEDY = SamplingParams()


def slot_key(base_key, rid: int):
    """Per-request PRNG key: fold the request id into the engine's seed."""
    return jax.random.fold_in(base_key, rid)


def sample_tokens(
    logits: jax.Array,      # (B, V) float
    keys: jax.Array,        # (B, 2) uint32 per-slot base keys
    positions: jax.Array,   # (B,) int32 — folded in for per-step streams
    temperature: jax.Array,  # (B,) float32
    top_k: jax.Array,        # (B,) int32, 0 = off
    top_p: jax.Array,        # (B,) float32, 1.0 = off
) -> jax.Array:
    """Draw one token per row.  Rows with ``temperature == 0`` take argmax."""
    with jax.named_scope("sample"):
        logits = logits.astype(jnp.float32)
        b, v = logits.shape
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]

        # top-k: keep values >= the k-th largest (ties may keep a few extra)
        desc = -jnp.sort(-scaled, axis=-1)
        kth = jnp.take_along_axis(
            desc, jnp.clip(top_k - 1, 0, v - 1)[:, None], axis=-1
        )
        keep = jnp.where((top_k > 0)[:, None], scaled >= kth, True)

        # top-p (nucleus): smallest prefix of the sorted distribution whose
        # mass reaches top_p; the first token always survives
        probs = jax.nn.softmax(scaled, axis=-1)
        sp = -jnp.sort(-probs, axis=-1)
        csum = jnp.cumsum(sp, axis=-1)
        n_keep = jnp.maximum(jnp.sum(csum - sp < top_p[:, None], axis=-1), 1)
        thr = jnp.take_along_axis(sp, (n_keep - 1)[:, None], axis=-1)
        keep &= probs >= thr

        masked = jnp.where(keep, scaled, NEG_INF)
        step_keys = jax.vmap(jax.random.fold_in)(keys, positions)
        gumbel = jax.vmap(
            lambda k: jax.random.gumbel(k, (v,), jnp.float32)
        )(step_keys)
        sampled = jnp.argmax(masked + gumbel, axis=-1).astype(jnp.int32)
        return jnp.where(temperature <= 0, greedy, sampled)
