"""Backend dispatch of the packed serving path.

On a TPU the packed quant modes must serve through the compiled Pallas
kernels and build none of the f32 shortcut grids that only pay off on CPU;
elsewhere they keep the jnp references.  A CPU process cannot own a TPU,
so these tests steer ``jax.default_backend`` inside the test while the
engine is built, then run what was built in Pallas interpret mode: the
kernel path must emit exactly the reference path's tokens.
"""

import dataclasses

import jax
import pytest

from repro.core.packed_params import is_dsp_tuned_leaf, quantize_for_serving
from repro.models import transformer as T
from repro.models.registry import get_config
from repro.serving import Engine, ServeConfig

CFG = dataclasses.replace(get_config("qwen1.5-110b", smoke=True),
                          dtype="float32")
PARAMS = T.init_params(jax.random.PRNGKey(0), CFG)
PROMPTS = [[3, 5, 7, 9], [2, 4]]


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _scfg(**kw):
    return ServeConfig(n_slots=2, max_len=32, prefill_chunk=4, **kw)


def _f32_grids(tree) -> list[str]:
    leaves = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=is_dsp_tuned_leaf
    )[0]
    found = []
    for path, leaf in leaves:
        if is_dsp_tuned_leaf(leaf):
            if leaf.w_f32 is not None:
                found.append(jax.tree_util.keystr(path))
        elif "w_f32" in jax.tree_util.keystr(path):
            found.append(jax.tree_util.keystr(path))
    return found


def test_use_kernel_resolves_from_backend(on_tpu):
    assert _scfg().use_kernel is True
    # tensor parallelism keeps the shard_map'd jnp references
    assert _scfg(tp=2).use_kernel is False
    assert _scfg(use_kernel=False).use_kernel is False
    with pytest.raises(ValueError, match="tensor parallel"):
        _scfg(tp=2, use_kernel=True)


def test_use_kernel_off_the_chip():
    assert jax.default_backend() != "tpu"
    assert _scfg().use_kernel is False


@pytest.mark.parametrize("mode", ["int4_packed", "dsp_tuned"])
def test_no_f32_grids_on_tpu(on_tpu, mode):
    q = quantize_for_serving(PARAMS, mode)
    assert _f32_grids(q) == []


@pytest.mark.parametrize("mode", ["int4_packed", "dsp_tuned"])
def test_f32_grids_stay_on_cpu(mode):
    assert _f32_grids(quantize_for_serving(PARAMS, mode))


@pytest.mark.parametrize("mode", ["int4_packed", "dsp_tuned"])
def test_tpu_built_engine_serves_through_kernels(monkeypatch, mode):
    """An engine built as on a TPU decodes through ``pallas_call`` and
    emits the reference engine's tokens (run here in interpret mode)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chip = Engine(CFG, PARAMS, _scfg(quant_mode=mode))
    monkeypatch.undo()
    assert chip.scfg.use_kernel and chip.cfg.quant.use_kernel
    assert _f32_grids(chip.params) == []
    want = Engine(CFG, PARAMS, _scfg(quant_mode=mode)).generate(
        PROMPTS, max_new=4
    )
    assert chip.generate(PROMPTS, max_new=4) == want
    state = jax.device_put({
        "tokens": chip.last_token, "positions": chip.positions,
        "active": chip.active, "keys": chip._keys,
        "temperature": chip._temperature, "top_k": chip._top_k,
        "top_p": chip._top_p,
    })
    jaxpr = str(jax.make_jaxpr(chip._decode_step)(
        chip.params, chip.cache, state
    ))
    assert "pallas_call" in jaxpr
