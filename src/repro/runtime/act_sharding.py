"""Activation sharding constraints (opt-in, mesh-aware, model-agnostic).

Without explicit constraints XLA's sharding propagation may keep FSDP dim
shards on weights and reshard *activations* instead (f32 all-to-alls on the
residual stream — observed in the baseline dry-run, see EXPERIMENTS.md
§Perf iteration 1).  ``activation_sharding(mesh)`` installs a thread-local
policy; ``constrain(x, kind)`` is a no-op unless a policy is active, so
model code stays pure and mesh-free.

Kinds: ``residual`` (B,S,D) → P(dp, None, None); ``heads`` (B,S,H,hd) and
``hidden`` (B,S,F) → model-sharded feature dim; ``expert`` (E,C,D) →
P(model, None, None).
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["activation_sharding", "constrain"]

_STATE = threading.local()


def _policy():
    return getattr(_STATE, "policy", None)


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, group_shardings=None):
    """``group_shardings``: optional NamedSharding pytree for ONE sliced
    scan group; when set, the scan body re-pins its sliced params so XLA's
    while-loop layout pass cannot reshard the parameter stack per step."""
    fsdp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    fs = fsdp if len(fsdp) > 1 else fsdp[0]
    n_fsdp = 1
    for a in fsdp:
        n_fsdp *= mesh.shape[a]
    n_model = mesh.shape["model"]

    def spec_for(kind: str, shape: tuple[int, ...]) -> P | None:
        batch = fs if shape[0] % n_fsdp == 0 else None
        if kind == "residual":
            return P(*((batch,) + (None,) * (len(shape) - 1)))
        if kind == "hidden":
            feat = "model" if shape[-1] % n_model == 0 else None
            return P(*((batch,) + (None,) * (len(shape) - 2) + (feat,)))
        if kind == "heads":  # (B, S, H, hd)
            # heads on model when divisible; otherwise batch-only — an
            # hd-sharded fallback would force S²-sized score psums
            # (measured 1.2e13 B/step on starcoder2 — §Perf cell B)
            if shape[1] > 1 and shape[2] % n_model == 0:
                return P(batch, None, "model", None)
            return P(batch, None, None, None)
        if kind == "expert":  # (E, C, D)
            e = "model" if shape[0] % n_model == 0 else None
            return P(e, None, None)
        if kind == "scores_decode":  # (B, H, q, S) or grouped (B, G, R, q, S):
            # shard the key axis so softmax runs distributed (psum of lse)
            # instead of XLA gathering the whole KV cache per decoded token
            s = "model" if shape[-1] % n_model == 0 else None
            return P(batch, *(None,) * (len(shape) - 2), s)
        return None

    _STATE.policy = (mesh, spec_for, group_shardings)
    try:
        yield
    finally:
        _STATE.policy = None


def constrain(x: jax.Array, kind: str) -> jax.Array:
    pol = _policy()
    if pol is None:
        return x
    mesh, spec_for, _ = pol
    spec = spec_for(kind, x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_group_params(gp):
    """Pin a sliced scan-group param tree to its per-group shardings."""
    pol = _policy()
    if pol is None or pol[2] is None:
        return gp
    return jax.tree.map(
        lambda t, s: jax.lax.with_sharding_constraint(t, s), gp, pol[2]
    )
