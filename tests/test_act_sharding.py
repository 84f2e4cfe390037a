"""Activation-sharding policy rules (pure spec logic, no devices)."""

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.runtime.act_sharding import activation_sharding, constrain


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def _spec_for(mesh, kind, shape):
    with activation_sharding(mesh):
        from repro.runtime import act_sharding

        _, spec_for, _ = act_sharding._policy()
        return spec_for(kind, shape)


MESH = FakeMesh({"data": 16, "model": 16})
MESHP = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_residual_batch_sharded():
    assert _spec_for(MESH, "residual", (256, 4096, 3840)) == P("data", None, None)
    assert _spec_for(MESHP, "residual", (256, 4096, 3840)) == P(("pod", "data"), None, None)


def test_residual_indivisible_batch_replicates():
    assert _spec_for(MESH, "residual", (7, 64, 128)) == P(None, None, None)


def test_hidden_feature_sharded():
    assert _spec_for(MESH, "hidden", (256, 4096, 10240)) == P("data", None, "model")


def test_heads_divisible():
    assert _spec_for(MESH, "heads", (256, 4096, 32, 120)) == P("data", None, "model", None)


def test_heads_indivisible_batch_only():
    # 36 heads on 16-way model: hd-shard fallback would force S^2 psums;
    # only the (divisible) batch axis is sharded
    assert _spec_for(MESH, "heads", (32, 4096, 36, 128)) == P("data", None, None, None)


def test_heads_decode_single_position():
    assert _spec_for(MESH, "heads", (128, 1, 64, 128)) == P("data", None, None, None)


def test_scores_decode_seq_sharded():
    spec = _spec_for(MESH, "scores_decode", (128, 64, 1, 32768))
    assert spec == P("data", None, None, "model")


def test_scores_decode_grouped_seq_sharded():
    # grouped-query scores (B, G, R, q, S): batch first, key axis last
    spec = _spec_for(MESH, "scores_decode", (128, 8, 4, 1, 32768))
    assert spec == P("data", None, None, None, "model")
    spec = _spec_for(MESH, "scores_decode", (7, 4, 9, 16, 4100))
    assert spec == P(None, None, None, None, None)


def test_constrain_is_noop_without_policy():
    x = jnp.ones((4, 4))
    assert constrain(x, "residual") is x


def test_expert_sharding():
    assert _spec_for(MESH, "expert", (16, 1024, 6144)) == P("model", None, None)
    assert _spec_for(MESH, "expert", (6, 64, 64)) == P(None, None, None)
