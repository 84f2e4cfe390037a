"""The reduction from a profiler trace to busy and idle time, operation
totals, idle gaps named by host spans and program runs."""

import json
from pathlib import Path

import pytest

from chipbench import trace
from chipbench.bench import load_reader, HERE

DATA = Path(__file__).parent / "data"

TOY = {
    "devices": {"/device:TPU:0": [
        ("%while.1 = (s32[]) while(...)", 100, 460),  # holds the next three
        ("%fusion.1 = bf16[8] fusion(...)", 100, 200),
        ("%int4_matmul.7 = s32[128,256] custom-call(...), "
         'custom_call_target="tpu_custom_call"', 200, 300),
        ("%fusion.2 = bf16[8] fusion(...)", 400, 450),
        ("%int4_matmul.9 = s32[128,256] custom-call(...), "
         'custom_call_target="tpu_custom_call"', 900, 1000),
        ("%fusion.3 = bf16[8] fusion(...)", 1040, 1100),  # past the end
    ]},
    "modules": {"/device:TPU:0": [
        ("jit__decode_step(12)", 100, 460), ("jit__lm_head(3)", 890, 1000),
        ("jit__decode_step(12)", 1040, 1100),  # not inside the window
    ]},
    "spans": [("chipbench.step", 50, 700),
              ("chipbench.drain_stream", 600, 800),
              ("chipbench.step", 800, 1050)],
}


def test_toy_trace_known_numbers():
    red = trace.reduce(TOY)
    assert red["window_s"] == pytest.approx(1000e-9)
    # union: [100, 460] + [900, 1000] + [1040, 1050]
    assert red["busy_s"] == pytest.approx(470e-9)
    kinds = dict(red["top_ops"])
    # self times: the loop less what it holds
    assert kinds["while"] == pytest.approx(110e-9)
    assert kinds["int4_matmul"] == pytest.approx(200e-9)
    assert kinds["fusion"] == pytest.approx(160e-9)
    assert red["top_ops"][0][0] == "int4_matmul"
    gaps = red["idle_gaps"]
    assert gaps[0] == ["drain_stream", pytest.approx(440e-9)]
    assert [round(g[1] * 1e9) for g in gaps] == [440, 50, 40]
    assert red["module_runs"] == {"jit__decode_step": 1, "jit__lm_head": 1}
    assert red["module_s"] == {"jit__decode_step": pytest.approx(360e-9),
                               "jit__lm_head": pytest.approx(110e-9)}


def test_op_kind():
    assert trace.op_kind('%int4_matmul.53 = s32[128,10240] custom-call(x)') \
        == "int4_matmul"
    assert trace.op_kind("%while.24 = (s32[]) while(t)") == "while"


def test_no_spans_or_no_device_reads_nothing():
    assert trace.reduce(dict(TOY, spans=[])) == {}
    assert trace.reduce(dict(TOY, devices={})) == {}


def test_readers_on_the_toy_trace():
    red = trace.reduce(TOY)
    ctx = type("Ctx", (), {})()
    ctx.trace = red
    assert load_reader(HERE, "device_idle_share")(ctx) == pytest.approx(53.0)
    ctx.trace = None
    assert load_reader(HERE, "device_idle_share")(ctx) is None


def test_step_readers_on_the_toy_trace():
    """Device time per decode program run and per prompt token, from the
    trace's program line; nothing to read gives nothing."""
    ctx = type("Ctx", (), {})()
    ctx.trace = trace.reduce(TOY)
    ctx.counters = {"prefill_tokens": 4}
    assert load_reader(HERE, "decode_step_ms")(ctx) == pytest.approx(360e-6)
    # no prefill program ran inside the window
    assert load_reader(HERE, "prefill_ms_per_tok")(ctx) is None
    ctx.trace = dict(ctx.trace, module_s={"jit__prefill_chunk": 2e-3},
                     module_runs={"jit__prefill_chunk": 1})
    assert load_reader(HERE, "prefill_ms_per_tok")(ctx) == pytest.approx(0.5)
    assert load_reader(HERE, "decode_step_ms")(ctx) is None
    ctx.counters = {"prefill_tokens": 0}
    assert load_reader(HERE, "prefill_ms_per_tok")(ctx) is None


def test_hbm_share_reads_the_window_not_the_build():
    ctx = type("Ctx", (), {})()
    ctx.memory = {"in_use": 4e9, "limit": 16e9}
    assert load_reader(HERE, "hbm_in_use_share")(ctx) == pytest.approx(25.0)
    ctx.memory = {"in_use": 4e9, "limit": None}
    assert load_reader(HERE, "hbm_in_use_share")(ctx) is None


def test_recorded_chip_trace_excerpt():
    """10 ms of a traced run of h2o-danube-3-4b (int4, 16 slots, one-token
    prefill on the slot engine) on one TPU v5 lite, inside one prefill call (operation names cut to their HLO
    instruction name): the reduction against a plain sweep over the
    events and against the numbers read off the excerpt."""
    ex = json.loads((DATA / "trace_chat_excerpt.json").read_text())
    red = trace.reduce(ex)
    lo = min(s for _, s, _ in ex["spans"])
    hi = max(e for _, _, e in ex["spans"])
    ops = next(iter(ex["devices"].values()))
    edges = sorted({lo, hi} | {min(max(t, lo), hi) for _, s, e in ops
                                for t in (s, e)})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= e for _, s, e in ops))
    assert red["busy_s"] == pytest.approx(busy * 1e-9)
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.010)   # the device never idles
    assert red["idle_gaps"] == []
    kernel = sum(min(e, hi) - max(s, lo) for n, s, e in ops
                 if n.startswith("%int4_matmul") and min(e, hi) > max(s, lo))
    got = sum(t for n, t in red["ops_s"].items()
              if n.startswith("%int4_matmul"))
    assert got == pytest.approx(kernel * 1e-9)
    assert red["top_ops"][0] == ["int4_matmul", pytest.approx(3.957293e-3)]
    # the prefill program runs on past both ends of the excerpt
    assert red["module_runs"] == {} and red["module_s"] == {}


def test_load_reads_host_spans_from_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    tr = trace.load(path)
    assert [s[0] for s in tr["spans"]] == ["chipbench.step"]
    assert tr["devices"] == {}   # a CPU has no TPU plane


def test_kernel_roofline_reader_on_the_toy_trace():
    from chipbench import roofline

    model = dict(n_layers=1, d_model=256, n_heads=2, n_kv_heads=1,
                 d_ff=512, vocab_size=1000)
    peak = roofline.peaks("TPU v5 lite")
    ctx = type("Ctx", (), {})()
    ctx.trace, ctx.peaks = trace.reduce(TOY), peak
    ctx.model, ctx.serve = model, {"n_slots": 16, "prefill_chunk": 16}
    need = (roofline.forward_min_time_s(model, 16, peak, True)
            + roofline.forward_min_time_s(dict(model, n_layers=0), 16, peak,
                                          True))
    got = load_reader(HERE, "packed_kernel_roofline")(ctx)
    assert got == pytest.approx(100 * need / 200e-9)
    ctx.trace = dict(ctx.trace, ops_s={"%fusion.1 = f()": 1.0})
    assert load_reader(HERE, "packed_kernel_roofline")(ctx) is None
