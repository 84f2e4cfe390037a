"""Compile the main-path Pallas kernels for a TPU v5e that is described, not
attached, at h2o-danube-3-4b layer shapes.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
unaligned block shapes, vector shape casts, int8 shifts, too much VMEM.
Where the TPU compiler (the ``libtpu`` package) is installed, each case
here lowers and compiles the kernel with ``interpret=False`` against a
``v5e:2x2`` topology description — about a second per kernel, no chip
needed.  Without ``libtpu`` the module skips; with it, a topology that
cannot be described is a failure.

The topology is described only inside the module fixture: the TPU library
may be loaded by one process at a time, and every pytest-xdist worker
imports this module, so nothing here may touch it at import time.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.addpack_acc import addpack_accumulate
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int4_matmul import DEFAULT_BLOCK as INT4_BLOCK, int4_matmul
from repro.kernels.packed_matmul import (
    DECODE_BLOCK,
    default_block_for,
    packed_matmul_prepacked,
)
from repro.kernels.ref import INT4_EXACT, PackedDotSpec

# h2o-danube-3-4b: d_model 3840, d_ff 10240, 32 heads of 128, vocab 32000
D_MODEL, D_FF, N_HEADS, HEAD_DIM = 3840, 10240, 32, 128
LAYERS = {"up": (D_MODEL, D_FF), "down": (D_FF, D_MODEL)}
PHASES = {"decode": 8, "prefill": 256}
# the exact preset and the plan the a4w4 tuner ranks first when exact
# plans get no f32 shortcut (the chip's ranking): mr+full, two columns
PLANS = {
    "exact": INT4_EXACT,
    "mr": PackedDotSpec(4, 4, 10, 32, "mr+full", 3, n_columns=2),
}


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("the TPU compiler (libtpu) is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"
    return compiled


def _arg(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("layer", sorted(LAYERS))
@pytest.mark.parametrize("phase", sorted(PHASES))
def test_int4_matmul_compiles(one_chip, phase, layer):
    m, (k, n) = PHASES[phase], LAYERS[layer]
    block = DECODE_BLOCK if m <= DECODE_BLOCK[0] else INT4_BLOCK  # as served
    _compile(
        lambda x, w: int4_matmul(x, w, block=block, interpret=False),
        _arg(one_chip, (m, k), jnp.int8),
        _arg(one_chip, (k // 2, n), jnp.uint8),
    )


@pytest.mark.parametrize("quantize", ["fused", "staged"])
@pytest.mark.parametrize("plan", sorted(PLANS))
@pytest.mark.parametrize("phase", sorted(PHASES))
def test_packed_matmul_prepacked_compiles(one_chip, phase, plan, quantize):
    """Weights as prepacked words (+ contamination operands for the mr
    plan); activations either raw f32 quantized in the kernel prologue
    (the serving call) or already quantized."""
    spec = PLANS[plan]
    m, (k, n) = PHASES[phase], LAYERS["up"]
    fused = quantize == "fused"
    shapes = [
        _arg(one_chip, (m, k), jnp.float32 if fused else jnp.int32),
        _arg(one_chip, (m, 1), jnp.float32),
        _arg(one_chip, (k // spec.chunk, spec.n_pairs, n), jnp.int32),
    ]
    if spec.uses_mr:
        shapes.append(_arg(
            one_chip, (k // spec.chunk, spec.n_pairs, 2, n), jnp.int32
        ))

    def call(x, x_scale, words, wsc=None):
        return packed_matmul_prepacked(
            x, words, wsc, spec=spec, block=default_block_for(m, spec),
            interpret=False, x_scale=x_scale if fused else None,
            x_zp=1 << (spec.bits_a - 1) if fused else None,
        )

    _compile(call, *shapes)


def test_flash_attention_compiles(one_chip):
    qkv = _arg(one_chip, (1, N_HEADS, PHASES["prefill"], HEAD_DIM),
               jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False),
             qkv, qkv, qkv)


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_addpack_accumulate_compiles(one_chip, phase):
    _compile(
        lambda t: addpack_accumulate(t, interpret=False),
        _arg(one_chip, (PHASES[phase], 2, D_FF), jnp.int32),
    )
