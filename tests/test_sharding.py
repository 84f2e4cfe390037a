"""Sharding policy rules + an 8-device subprocess dry-run smoke + elastic
resharding restore (different device count than saved)."""

import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

from repro.runtime.sharding import (
    batch_pspec,
    cache_pspec,
    fsdp_axes,
    linear_partition,
    param_pspec,
)


class FakeMesh:
    """Duck-typed mesh for rule tests (shape dict + axis names)."""

    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH1 = FakeMesh({"data": 16, "model": 16})
MESH2 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_column_parallel_qkv():
    spec = param_pspec("groups/attn/wq/w", (80, 8192, 8192), MESH1)
    assert spec == P(None, "data", "model")


def test_row_parallel_out():
    spec = param_pspec("groups/attn/wo/w", (80, 8192, 8192), MESH2)
    assert spec == P(None, "model", ("pod", "data"))


def test_embed_vocab_parallel():
    assert param_pspec("embed/w", (152064, 8192), MESH1) == P("model", "data")


def test_norms_replicated():
    assert param_pspec("groups/ln1/scale", (80, 8192), MESH1) == P(None, None)


def test_expert_parallel_moe():
    spec = param_pspec("groups/moe/up", (40, 16, 6144, 10752), MESH1)
    assert spec[1] == "model"  # expert axis on model (EP)


def test_indivisible_dims_replicate():
    # whisper vocab 51866 is not divisible by 16: must not shard on model
    spec = param_pspec("lm_head/w", (1280, 51866), MESH1)
    assert "model" not in spec


def test_cache_seq_parallel_for_batch1():
    # long_500k: batch=1 -> shard the sequence axis (SP)
    spec = cache_pspec(MESH1, (4, 1, 524288, 8, 128), batch=1)
    assert spec[2] == "data"
    assert spec[1] is None


def test_cache_batch_parallel():
    spec = cache_pspec(MESH1, (40, 128, 32768, 8, 128), batch=128)
    assert spec[1] == "data"


def test_fsdp_axes_with_and_without_pod():
    assert fsdp_axes(MESH1) == ("data",)
    assert fsdp_axes(MESH2) == ("pod", "data")


def test_linear_partition_exact_token_match():
    # Megatron conventions shared with the serving TP wrapper
    assert linear_partition("groups/attn/wq/w") == "col"
    assert linear_partition("groups/mlp/up/w") == "col"
    assert linear_partition("lm_head/w") == "col"
    assert linear_partition("groups/attn/wo/w") == "row"
    assert linear_partition("groups/mlp/down/w") == "row"
    # unnamed roles replicate
    assert linear_partition("groups/ln1/scale") is None
    assert linear_partition("groups/moe/router/w") is None
    # exact token matching, never substring: 'groups' must not match
    # 'up' ("§Perf iteration 7" — the bug col-sharded every stacked
    # weight), nor 'wo_gated' match 'wo'
    assert linear_partition("groups/groupnorm/w") is None
    assert linear_partition("upstream/w") is None


def test_batch_pspec_divisibility():
    assert batch_pspec(MESH1, 128) == P("data", None)
    assert batch_pspec(MESH2, 64) == P(("pod", "data"), None)
    # indivisible batch replicates rather than padding implicitly
    assert batch_pspec(MESH1, 7) == P(None, None)
    assert batch_pspec(MESH2, 16) == P(None, None)  # 16 % 32 != 0


def test_cache_pspec_both_meshes():
    # attn KV (G, B, S, kv, hd): batch over the composite fsdp axis
    spec = cache_pspec(MESH2, (40, 64, 32768, 8, 128), batch=64)
    assert spec[1] == ("pod", "data")
    # batch=1 falls back to sequence parallelism on the same mesh
    spec = cache_pspec(MESH2, (4, 1, 524288, 8, 128), batch=1)
    assert spec[1] is None and spec[2] == ("pod", "data")
    # state caches (G, B, feat): batch on fsdp, biggest feature on model
    spec = cache_pspec(MESH1, (4, 128, 4096), batch=128, path="mamba")
    assert spec[1] == "data" and spec[2] == "model"


@pytest.mark.slow
def test_subprocess_8dev_dryrun_smoke(tmp_path):
    """End-to-end pjit on 8 fake devices in a subprocess (smoke config)."""
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models import transformer as T
        from repro.models.registry import get_config
        from repro.optim.adamw import AdamWConfig, adamw_init
        from repro.runtime.sharding import param_shardings
        from repro.launch.steps import make_train_step

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("qwen1.5-110b", smoke=True)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        shard = param_shardings(params, mesh)
        params = jax.tree.map(lambda x, s: jax.device_put(x, s), params, shard)
        state = {"params": params, "opt": adamw_init(params)}
        step = jax.jit(make_train_step(cfg, AdamWConfig(), mesh))
        batch = {
            "tokens": jnp.zeros((8, 16), jnp.int32),
            "labels": jnp.zeros((8, 16), jnp.int32),
        }
        with mesh:
            state, metrics = step(state, batch)
        assert jnp.isfinite(metrics["loss"]), metrics
        print("SUBPROCESS_OK", float(metrics["loss"]))
        """
    )
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)), timeout=480,
    )
    assert "SUBPROCESS_OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.slow
def test_elastic_reshard_restore(tmp_path):
    """Checkpoint saved from 1-device state restores onto an 8-device mesh
    (elastic restart), bit-exactly."""
    code = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.models import transformer as T
        from repro.models.registry import get_config
        from repro.runtime.sharding import param_shardings

        cfg = get_config("qwen1.5-110b", smoke=True)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        ck = Checkpointer(r"{tmp_path}")
        ck.save(1, params)

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        shard = param_shardings(params, mesh)
        restored, _ = ck.restore(1, params, shardings=shard)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        ndev = {{len(s.device_set) for s in jax.tree.leaves(jax.tree.map(lambda x: x.sharding, restored))}}
        assert max(ndev) == 8, ndev
        print("RESHARD_OK")
        """
    )
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)), timeout=480,
    )
    assert "RESHARD_OK" in out.stdout, out.stderr[-2000:]
