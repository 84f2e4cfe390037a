"""Tensor-parallel serving conformance + the replica front.

The mesh halves run in subprocesses (``XLA_FLAGS=--xla_force_host_
platform_device_count`` must be set before jax initializes, so the
parent process — which holds a 1-device jax — cannot host them): TP
decode must be **bit-identical** to the single-device engine in every
quant mode (DESIGN.md §4), and an illegal sharding must be rejected at
build with the violated certificate clause named.  The data-parallel
``ReplicaFront`` needs no mesh and is tested in-process.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.models import transformer as T
from repro.models.registry import get_config
from repro.serving import Engine, ReplicaFront, ServeConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# shared by the subprocess snippets: smoke config, f32 (bit-identity is
# asserted on tokens, but f32 keeps the reference arithmetic exact),
# tiny serving grid, greedy sampling
COMMON = """
import dataclasses, os
import jax
from repro.models import transformer as T
from repro.models.registry import get_config
from repro.serving import Engine, ServeConfig

CFG = dataclasses.replace(get_config("qwen1.5-110b", smoke=True),
                          dtype="float32")
PARAMS = T.init_params(jax.random.PRNGKey(0), CFG)
PROMPTS = [[3, 5, 7], [2, 4]]

def scfg(**kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_chunk", 4)
    return ServeConfig(**kw)
"""


def _run(n_devices: int, body: str, timeout: int = 540) -> str:
    code = (
        "import os\n"
        f'os.environ["XLA_FLAGS"] = '
        f'"--xla_force_host_platform_device_count={n_devices}"\n'
        + COMMON + textwrap.dedent(body)
    )
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=REPO, timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_tp2_int4_decode_bit_identity():
    """Fast-lane coverage: int4_packed decode on a 2-way mesh emits the
    single-device tokens exactly."""
    out = _run(2, """
    ref = Engine(CFG, PARAMS, scfg(quant_mode="int4_packed")).generate(
        PROMPTS, max_new=6)
    tp = Engine(CFG, PARAMS, scfg(quant_mode="int4_packed", tp=2)).generate(
        PROMPTS, max_new=6)
    assert tp == ref, (tp, ref)
    print("TP2_OK")
    """)
    assert "TP2_OK" in out


@pytest.mark.slow
@pytest.mark.parametrize("tp", [2, 8])
def test_tp_decode_bit_identity_all_modes(tp):
    """The acceptance matrix: every quant mode, tokens bit-identical to
    tp=1 on 2- and 8-way host meshes.  dsp_mixed pins its candidate set
    to (4,4): the 8-bit width families have no plan whose widened spec
    fits one int32 word under this sharding (see test_tp_packed), and
    the allocator is not allowed to silently change widths per tp."""
    out = _run(tp, f"""
    MODES = {{
        "native": {{}},
        "int4_packed": {{}},
        "dsp_tuned": {{}},
        "dsp_mixed": dict(plan_bits="auto", width_candidates=((4, 4),),
                          calib_tokens=8),
    }}
    for mode, kw in MODES.items():
        ref = Engine(CFG, PARAMS, scfg(quant_mode=mode, **kw)).generate(
            PROMPTS, max_new=6)
        got = Engine(CFG, PARAMS,
                     scfg(quant_mode=mode, tp={tp}, **kw)).generate(
            PROMPTS, max_new=6)
        assert got == ref, (mode, got, ref)
        print("MODE_OK", mode)
    print("ALL_MODES_OK")
    """)
    assert "ALL_MODES_OK" in out
    for mode in ("native", "int4_packed", "dsp_tuned", "dsp_mixed"):
        assert f"MODE_OK {mode}" in out


def test_illegal_sharding_rejected_with_clause():
    """A plan table selected for one device (the INT4_EXACT preset sits
    at the int32 accumulation ceiling) cannot be row-sharded: the build
    must fail citing the violated certificate clause, naming the leaf."""
    out = _run(2, """
    from repro.core.packed_params import quantize_for_serving
    from repro.launch.mesh import make_serving_mesh
    from repro.runtime.tp_packed import shard_params_tp
    from repro.tuning import plan_linear_layers

    table = plan_linear_layers(PARAMS, a_bits=4, w_bits=4,
                               error_budget=0.0, shard_groups=1)
    q = quantize_for_serving(PARAMS, "dsp_tuned", plans=table)
    mesh = make_serving_mesh(2)
    try:
        shard_params_tp(q, mesh)
        raise SystemExit("sharding was not rejected")
    except ValueError as e:
        msg = str(e)
    assert "illegal row sharding" in msg, msg
    assert "certificate clause" in msg, msg
    assert "int32-accumulator" in msg, msg
    print("REJECT_OK")

    # and use_kernel has no cross-device reduction stage: rejected too
    try:
        shard_params_tp(q, mesh, use_kernel=True)
        raise SystemExit("use_kernel was not rejected")
    except ValueError as e:
        assert "use_kernel" in str(e)
    print("KERNEL_REJECT_OK")
    """)
    assert "REJECT_OK" in out and "KERNEL_REJECT_OK" in out


def test_replicas_sit_on_their_own_device_slices():
    """Replica r of a ReplicaFront runs on devices [r·tp, (r+1)·tp): four
    tp=1 replicas on four devices hold their weights and cache on one
    device each, two tp=2 replicas each span their own pair — and routing
    never changes tokens."""
    out = _run(4, """
    from repro.serving import ReplicaFront

    def devices_of(tree):
        return {d.id for a in jax.tree.leaves(tree) for d in a.devices()}

    ref = Engine(CFG, PARAMS, scfg(quant_mode="int4_packed")).generate(
        PROMPTS, max_new=4)
    for tp, n_rep in ((1, 4), (2, 2)):
        front = ReplicaFront(CFG, PARAMS, scfg(quant_mode="int4_packed", tp=tp),
                             n_replicas=n_rep)
        for r, eng in enumerate(front.replicas):
            want = set(range(r * tp, (r + 1) * tp))
            assert devices_of(eng.params) == want, (tp, r)
            assert devices_of(eng.cache) == want, (tp, r)
        got = front.generate(PROMPTS * 2, max_new=4)
        assert [got[g] for g in sorted(got)] == [ref[0], ref[1]] * 2, got
        print("SLICES_OK", tp)
    """)
    assert "SLICES_OK 1" in out and "SLICES_OK 2" in out


# ---- replica front (in-process: no mesh required) --------------------------

CFG = dataclasses.replace(get_config("qwen1.5-110b", smoke=True),
                          dtype="float32")
PARAMS = T.init_params(jax.random.PRNGKey(0), CFG)
PROMPTS = [[3, 5, 7], [2, 4], [9, 11, 13, 15], [6, 8]]


def _scfg(**kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_chunk", 4)
    return ServeConfig(**kw)


def test_replica_front_routes_jsq_deterministically():
    front = ReplicaFront(CFG, PARAMS, _scfg(), n_replicas=2)
    grids = [front.submit(p, max_new=4) for p in PROMPTS]
    assert grids == [0, 1, 2, 3]  # the front owns a global rid namespace
    # equal-load ties break to the lowest index, so submissions alternate
    assert [front.replica_of(g) for g in grids] == [0, 1, 0, 1]


def test_replica_front_tokens_match_single_engine():
    """Routing affects latency, never content: every replica quantizes
    identical weights, so the front's outputs equal one engine's."""
    solo = Engine(CFG, PARAMS, _scfg()).generate(PROMPTS, max_new=4)
    front = ReplicaFront(CFG, PARAMS, _scfg(), n_replicas=2)
    outputs = front.generate(PROMPTS, max_new=4)
    assert sorted(outputs) == [0, 1, 2, 3]
    for grid in outputs:
        assert outputs[grid] == solo[grid], grid
    stats = front.stats()
    assert stats["n_replicas"] == 2
    assert stats["finished"] == len(PROMPTS)
    assert len(stats["replicas"]) == 2


def test_replica_front_validates_n_replicas():
    with pytest.raises(ValueError, match="n_replicas"):
        ReplicaFront(CFG, PARAMS, _scfg(), n_replicas=0)
