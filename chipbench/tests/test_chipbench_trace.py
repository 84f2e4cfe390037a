"""The reduction from a profiler trace to busy and idle time, operation
totals, idle gaps named by host spans and program runs."""

import json
from pathlib import Path

import pytest

from chipbench import trace
from chipbench.bench import HERE, load_family, load_reader

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

    dense = load_family(HERE, "dense")
    model = dict(n_layers=1, d_model=256, n_heads=2, n_kv_heads=1,
                 d_ff=512, vocab_size=1000)
    peak = roofline.peaks("TPU v5 lite")
    ctx = type("Ctx", (), {})()
    ctx.trace, ctx.peaks, ctx.family = trace.reduce(TOY), peak, dense
    ctx.model, ctx.serve = model, {"n_slots": 16, "prefill_chunk": 16}
    ctx.counters = {"prefill_rows": 0, "prefill_steps": 0}
    need = (dense.forward_min_time_s(model, 16, peak, True)
            + dense.forward_min_time_s(dict(model, n_layers=0), 16, peak,
                                       True))
    got = load_reader(HERE, "packed_kernel_roofline")(ctx)
    assert got == pytest.approx(100 * need / 200e-9)
    # a prefill program's rows are the scheduler's over its prefill steps
    ctx.trace = dict(ctx.trace, module_runs={"jit__prefill_chunk": 3})
    ctx.counters = {"prefill_rows": 5 * 256, "prefill_steps": 5}
    got = load_reader(HERE, "packed_kernel_roofline")(ctx)
    assert got == pytest.approx(
        100 * 3 * dense.forward_min_time_s(model, 256, peak, False) / 200e-9)
    ctx.counters = {"prefill_rows": 0, "prefill_steps": 0}
    assert load_reader(HERE, "packed_kernel_roofline")(ctx) is None
    ctx.trace = dict(ctx.trace, ops_s={"%fusion.1 = f()": 1.0})
    assert load_reader(HERE, "packed_kernel_roofline")(ctx) is None


# One decode program's optimized HLO, cut to the instructions TOY_SCOPED
# runs: a fusion holds the exponent of the scores (its fused body carries
# the same metadata), the gather of the KV window, a kernel in ``mlp``, a
# copy without metadata.
TOY_HLO = """HloModule jit__decode_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %exponential.4 = f32[8]{0} exponential(%param_0.1), metadata={op_name="jit(_decode_step)/while/body/closed_call/checkpoint/paged_attention/scores/exp" stack_frame_id=3}
}

ENTRY %main.9 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0), metadata={op_name="cache[\\'attn\\'][\\'pages_k\\']"}
  %while.1 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="jit(_decode_step)/while" stack_frame_id=1}
  %fusion.1 = f32[8]{0} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_decode_step)/while/body/closed_call/checkpoint/paged_attention/scores/exp" stack_frame_id=3}
  %gather.2 = f32[8]{0} gather(%p.1, %i.1), offset_dims={}, metadata={op_name="jit(_decode_step)/while/body/closed_call/checkpoint/paged_attention/kv_gather/gather" stack_frame_id=4}
  %int4_matmul.7 = s32[16,256]{1,0} custom-call(%a.1, %b.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode_step)/while/body/closed_call/checkpoint/mlp/jit(int4_matmul)/pallas_call" stack_frame_id=5}
  ROOT %copy.3 = f32[8]{0} copy(%p.1)
}
"""

TOY_SCOPED = dict(TOY, devices={"/device:TPU:0": [
    ("%while.1 = (s32[], f32[8]) while(...)", 100, 460),
    ("%fusion.1 = f32[8] fusion(...)", 100, 200),
    ("%gather.2 = f32[8] gather(...)", 200, 250),
    ("%int4_matmul.7 = s32[16,256] custom-call(...), "
     'custom_call_target="tpu_custom_call"', 250, 300),
    ("%mystery.9 = f32[8] fusion(...)", 300, 320),   # not in the text
    ("%copy.3 = f32[8] copy(...)", 400, 450),
    ("%int4_matmul.9 = s32[128,256] custom-call(...)", 900, 1000),
]})


@pytest.mark.parametrize("op_name, path", [
    ("jit(_decode_step)/while/body/closed_call/checkpoint/paged_attention/"
     "kv_gather/gather", "paged_attention/kv_gather"),
    ("jit(_decode_step)/lm_head/jit(int4_prepacked_matmul_f32)/mul",
     "lm_head"),
    ("jit(_prefill_chunk)/...d,...d->.../dot_general", ""),
    ("jit(_decode_step)/sample/vmap()/jit(clip)/min", "sample"),
    ("jit(f)/cond/branch_1_fun/moe/router/top_k", "moe/router"),
    ("cache['attn']['pages_k']", ""),
    ("", ""),
])
def test_scope_path(op_name, path):
    assert trace.scope_path(op_name) == path


def test_op_names_of_an_hlo_text():
    names = trace.op_names(TOY_HLO)
    assert names["fusion.1"] == names["exponential.4"] == (
        "jit(_decode_step)/while/body/closed_call/checkpoint/"
        "paged_attention/scores/exp")
    assert names["copy.3"] == ""
    assert names["p.1"] == "cache[\\'attn\\'][\\'pages_k\\']"
    assert "main.9" not in names and "fused_computation.1" not in names
    assert trace.instruction("%int4_matmul.7 = s32[16,256] custom-call(x)") \
        == "int4_matmul.7"


def test_scope_join_on_the_toy_trace():
    """Self time of each run's operations by scope path, joined through
    the program's text; a program with no text joins nothing."""
    red = trace.reduce(TOY_SCOPED, hlo={"jit__decode_step": TOY_HLO})
    scopes = red["scopes_s"]["jit__decode_step"]
    assert scopes == {
        "paged_attention/scores": pytest.approx(100e-9),
        "paged_attention/kv_gather": pytest.approx(50e-9),
        "mlp": pytest.approx(50e-9),
        # the loop's own 90 ns and the copy's 50
        "": pytest.approx(140e-9)}
    assert red["scope_matched"] == {
        "jit__decode_step": pytest.approx(340 / 360),
        "jit__lm_head": 0.0}
    assert red["scopes_s"]["jit__lm_head"] == {}
    # without texts nothing is joined, and the rest of the reduction stays
    bare = trace.reduce(TOY_SCOPED)
    assert bare["scope_matched"] == {"jit__decode_step": 0.0,
                                     "jit__lm_head": 0.0}
    assert {k: v for k, v in bare.items()
            if k not in ("scopes_s", "scope_matched")} == \
        {k: v for k, v in red.items() if k not in ("scopes_s",
                                                    "scope_matched")}


def test_decode_attention_reader_on_the_toy_trace():
    ctx = type("Ctx", (), {})()
    read = load_reader(HERE, "decode_attention_ms")
    ctx.trace = trace.reduce(TOY_SCOPED, hlo={"jit__decode_step": TOY_HLO})
    assert read(ctx) == pytest.approx(150e-6)     # ms per decode run
    ctx.trace = trace.reduce(TOY_SCOPED)
    assert read(ctx) is None
    ctx.trace = None
    assert read(ctx) is None


def test_program_texts_are_taken_after_warm_up():
    """The recorder notes each jitted program's shapes while the engine
    warms up, hands the engine its own programs back, and afterwards
    yields each program's optimized HLO with the model's scopes in its
    metadata, without compiling anything again."""
    import dataclasses

    import jax

    from chipbench.harness import Programs
    from chipbench.tests.tiny import config
    from repro.models.registry import get_config
    from repro.serving import ContinuousEngine, ServeConfig

    dense = load_family(HERE, "dense")
    conf = config("continuous")
    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["model"])
    params = dense.params(dense.Model.from_config(conf["model"]),
                          jax.random.PRNGKey(3))
    engine = ContinuousEngine(cfg, params, ServeConfig(**conf["serve"],
                                                       seed=3))
    sample = engine._sample
    programs = Programs(engine)
    for n in (7, 3):
        engine.submit(list(range(2, 2 + n)), max_new=3, admit=False)
    for _ in range(8):
        engine.step()
    programs.stop()
    assert engine._sample is sample
    assert "_decode_step" not in vars(engine)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, dur, **kw: compiles.append(event)
        if "backend_compile" in event else None)
    texts = programs.texts()
    assert not compiles
    assert {"jit__decode_step", "jit__prefill_chunk", "jit__lm_head"} <= \
        set(texts)
    paths = {trace.scope_path(n)
             for n in trace.op_names(texts["jit__decode_step"]).values()}
    assert {"paged_attention/kv_gather", "paged_attention/scores", "mlp",
            "lm_head", "sample"} <= paths


def test_recorded_decode_program_scopes():
    """The decode program of the tiny continuous engine as the TPU
    compiler writes it (compiled for a described v5e, no chip; op times
    made up, see the file's ``source``): every operation is found in the
    program's text, and the paged-attention reader sums the operations
    whose ``op_name`` holds the scope, against a plain search of the
    text."""
    ex = json.loads((DATA / "decode_scopes_excerpt.json").read_text())
    text = ex["hlo"]["jit__decode_step"]
    red = trace.reduce(ex, hlo=ex["hlo"])
    assert red["scope_matched"] == {"jit__decode_step": 1.0}
    scopes = red["scopes_s"]["jit__decode_step"]
    assert {"paged_attention/kv_write", "paged_attention/kv_gather",
            "paged_attention/scores", "mlp", "lm_head", "sample"} <= \
        set(scopes)
    ops = ex["devices"]["/device:TPU:0"]
    want = 0
    for name, s, e in ops:
        head = name.split(" = ", 1)[0]
        line = next(ln for ln in text.splitlines()
                    if ln.lstrip().removeprefix("ROOT ").startswith(
                        head + " = "))
        if "/paged_attention/" in line.split("op_name=", 1)[-1]:
            want += e - s
    assert want > 0
    ctx = type("Ctx", (), {})()
    ctx.trace = red
    got = load_reader(HERE, "decode_attention_ms")(ctx)
    assert got == pytest.approx(want * 1e-6)   # one run, ns -> ms
    # self times add up to the time some operation ran
    busy, end = 0, 0
    for _, s, e in sorted(ops, key=lambda o: o[1]):
        busy += max(0, e - max(s, end))
        end = max(end, e)
    assert sum(scopes.values()) == pytest.approx(busy * 1e-9)
