"""Pipeline parallelism: 2-stage GPipe schedule over 8 fake devices must
equal the sequential forward."""

import os
import subprocess
import sys
import textwrap

import pytest


@pytest.mark.slow
def test_two_stage_pipeline_matches_sequential(tmp_path):
    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import transformer as T
        from repro.models.registry import get_config
        from repro.launch.mesh import make_mesh
        from repro.runtime.pipeline import pipeline_forward, split_stages

        cfg = dataclasses.replace(
            get_config("qwen1.5-110b", smoke=True), dtype="float32",
            remat="none",
        )
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        params = T.init_params(jax.random.PRNGKey(0), cfg)

        n_micro, mb, s = 3, 2, 16
        toks = jax.random.randint(jax.random.PRNGKey(1), (n_micro, mb, s), 0, cfg.vocab_size)

        # sequential reference
        ref = []
        for i in range(n_micro):
            logits, _, _ = T.forward(params, cfg, toks[i])
            ref.append(logits)
        ref = jnp.stack(ref)

        staged = split_stages(params, 2)
        with jax.set_mesh(mesh):
            got = pipeline_forward(staged, cfg, toks, mesh)
        err = float(jnp.abs(got - ref).max())
        assert err < 2e-3, err
        print("PIPELINE_OK", err)
        """
    )
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(__file__)), timeout=560,
    )
    assert "PIPELINE_OK" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])
