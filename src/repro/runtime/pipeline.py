"""GPipe-style pipeline parallelism over the "pod" mesh axis.

At 1000+ nodes the inter-pod links are the slow tier; pipelining the layer
stack across pods sends only per-microbatch activation boundaries over
those links ((mb, S, D) per tick) instead of FSDP parameter traffic.

Implementation: ``shard_map`` over the pod axis.  The layer-group stack is
split into ``n_stages`` contiguous stages (stage s owns groups
``[s·G/S, (s+1)·G/S)``, params sharded P('pod') on the leading axis).
Microbatches stream through the classic GPipe schedule: at tick ``t`` stage
``s`` runs microbatch ``t - s``; boundary activations hop one pod per tick
via ``ppermute``.  ``jax.grad`` differentiates straight through (the
transpose of ppermute is the reverse ppermute), so the same machinery
trains — this module provides the forward; the loss wrapper composes it.

CPU-testable: the correctness test runs the 2-stage schedule on 8 fake
host devices and asserts bit-equality with the sequential forward
(tests/test_pipeline.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models import transformer as T
from ..models.config import ModelConfig

__all__ = ["split_stages", "pipeline_forward"]


def split_stages(params, n_stages: int):
    """Reshape the group stack (G, ...) → (n_stages, G/S, ...)."""
    def resh(x):
        g = x.shape[0]
        assert g % n_stages == 0, (g, n_stages)
        return x.reshape(n_stages, g // n_stages, *x.shape[1:])

    out = dict(params)
    out["groups"] = jax.tree.map(resh, params["groups"])
    return out


def pipeline_forward(
    params,
    cfg: ModelConfig,
    tokens: jax.Array,  # (n_micro, mb, S)
    mesh: Mesh,
    axis: str = "pod",
):
    """Pipelined forward over ``axis``.  Returns logits (n_micro, mb, S, V).

    ``params`` must already be stage-split (`split_stages`) with the stage
    axis sharded over ``axis``; embedding/norm/lm_head replicate.
    """
    n_stages = mesh.shape[axis]
    n_micro = tokens.shape[0]
    ticks = n_micro + n_stages - 1

    def stage_apply(stage_groups, x):
        def body(carry, gp):
            y, _, _ = T._apply_group(gp, carry, cfg, positions, None, None)
            return y, None

        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1])[None], (x.shape[0], x.shape[1])
        )
        x, _ = jax.lax.scan(body, x, stage_groups)
        return x

    embed = params["embed"]["w"]
    lm_head = params.get("lm_head")
    final_norm = params["final_norm"]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), params["groups"]), P()),
        out_specs=P(),
        check_vma=False,
    )
    def run(stage_groups, toks):
        stage_groups = jax.tree.map(lambda t: t[0], stage_groups)  # local stage
        sid = jax.lax.axis_index(axis)
        mb, s = toks.shape[1:]
        d = cfg.d_model
        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]

        buf = jnp.zeros((mb, s, d), dtype)  # incoming activation register
        outputs = jnp.zeros((n_micro, mb, s, d), dtype)

        def tick(t, carry):
            buf, outputs = carry
            # stage 0 injects microbatch t (others use the received buffer)
            midx = jnp.clip(t, 0, n_micro - 1)
            injected = embed[toks[midx]].astype(dtype)
            x = jnp.where(sid == 0, injected, buf)
            y = stage_apply(stage_groups, x)
            # last stage commits microbatch t-(n_stages-1) when valid
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (sid == n_stages - 1)
            outputs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_idx, 0, n_micro - 1), 0
                ),
                lambda o: o,
                outputs,
            )
            # boundary hop: stage s -> s+1 (ring; last->0 value is unused)
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return buf, outputs

        _, outputs = jax.lax.fori_loop(0, ticks, tick, (buf, outputs))
        # only the last stage holds real outputs; share them along the axis
        outputs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis,
        )
        return outputs

    acts = run(params["groups"], tokens)

    # final norm + logits (replicated epilogue)
    from ..models.layers import rmsnorm

    x = rmsnorm(final_norm, acts, cfg.norm_eps)
    if cfg.tie_embeddings or lm_head is None:
        logits = x.astype(jnp.float32) @ embed.T.astype(jnp.float32)
    else:
        logits = x.astype(jnp.float32) @ lm_head["w"].astype(jnp.float32)
    return logits
