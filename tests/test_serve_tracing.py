"""The serving path's own tracing: named scopes in the step programs' HLO
metadata, ``serve.*`` profiler spans nested at the engine's layer
boundaries, the row counters beside the token counters, and the step
clock that feeds the per-phase rates and the straggler detector."""

import dataclasses
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.models import transformer as T
from repro.models.registry import get_config
from repro.serving import ContinuousEngine, Engine, ServeConfig

CFG = dataclasses.replace(get_config("qwen1.5-110b", smoke=True),
                          dtype="float32")
SLOTS, CHUNK = 4, 4


@pytest.fixture(scope="module")
def engine():
    """One engine for the module, its step programs compiled by one
    request before any test (and outside any profile)."""
    eng = ContinuousEngine(CFG, T.init_params(jax.random.PRNGKey(0), CFG),
                           ServeConfig(n_slots=SLOTS, max_len=32,
                                       prefill_chunk=CHUNK, page_size=8))
    eng.generate([[1, 2, 3]], max_new=2)
    return eng


@pytest.fixture(scope="module")
def slot_engine():
    """The slot engine, warmed up as ``engine`` is."""
    eng = Engine(CFG, T.init_params(jax.random.PRNGKey(0), CFG),
                 ServeConfig(n_slots=SLOTS, max_len=32, prefill_chunk=CHUNK))
    eng.generate([[1, 2, 3]], max_new=2)
    return eng


def _drain(eng):
    while eng.active.any() or eng.scheduler.n_queued:
        eng.step()


def test_decode_step_hlo_names_its_scopes(engine):
    """Lowered only: the names are op metadata of the step program."""
    engine._push_state(np.zeros(SLOTS, bool))
    lowered = ContinuousEngine._decode_step.lower(
        engine, engine.params, engine.cache, engine._dev_state)
    text = lowered.as_text(dialect="hlo", debug_info=True)
    for scope in ("paged_attention/kv_gather", "paged_attention/kv_write",
                  "paged_attention/scores", "mlp/", "lm_head/", "sample/"):
        assert scope in text, scope


def _spans(path):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


# each span and the span it nests in
PARENT = {
    "serve.admit.request": "serve.admit",
    "serve.prefill.inputs": "serve.prefill",
    "serve.prefill.dispatch": "serve.prefill",
    "serve.first_token": "serve.prefill",
    "serve.decode.pages": "serve.decode",
    "serve.decode.push": "serve.decode",
    "serve.decode.dispatch": "serve.decode",
    "serve.decode.wait": "serve.decode",
    "serve.decode.emit": "serve.decode",
    "serve.finish": "serve.decode.emit",
}


def test_step_writes_nested_serve_spans(engine, tmp_path):
    """Two steps under the profiler: the first admits, prefills the whole
    prompt and samples its first token; the second decodes the last
    token and finishes the request."""
    rid = engine.submit([3, 1, 4], max_new=2, admit=False)
    jax.profiler.start_trace(str(tmp_path))
    engine.step()
    engine.step()
    jax.profiler.stop_trace()
    spans = _spans(next(Path(tmp_path).rglob("*.xplane.pb")))
    names = {n for n, *_ in spans}
    assert set(PARENT) | set(PARENT.values()) <= names
    for name, s, e, _ in spans:
        if name in PARENT:
            assert any(p == PARENT[name] and ps <= s and e <= pe
                       for p, ps, pe, _ in spans), name
    # the request's spans carry its id
    for name in ("serve.admit.request", "serve.finish"):
        assert [st.get("rid") for n, *_, st in spans if n == name] == [rid]
    assert not engine.active.any()


@pytest.mark.parametrize("fixture", ["engine", "slot_engine"])
def test_row_counters_count_every_lane_of_every_call(fixture, request,
                                                     monkeypatch):
    """3 of 4 lanes busy: each prefill call computes SLOTS x CHUNK rows
    and each decode call SLOTS rows, real or not, on either engine."""
    engine = request.getfixturevalue(fixture)
    calls = {"prefill": 0, "decode": 0}

    def counted(kind, fn):
        def call(*args):
            calls[kind] += 1
            return fn(*args)
        return call

    monkeypatch.setattr(engine, "_prefill_chunk",
                        counted("prefill", engine._prefill_chunk))
    monkeypatch.setattr(engine, "_decode_step",
                        counted("decode", engine._decode_step))
    sched = engine.scheduler
    before = (sched.prefill_rows, sched.decode_rows, sched.prefill_tokens,
              sched.decode_tokens)
    prompts = [[2] * 6, [3] * 3, [4] * 9]
    for p in prompts:
        engine.submit(p, max_new=4, admit=False)
    _drain(engine)
    assert calls["prefill"] == 3 and calls["decode"] > 0
    assert sched.prefill_rows - before[0] == SLOTS * CHUNK * calls["prefill"]
    assert sched.decode_rows - before[1] == SLOTS * calls["decode"]
    assert sched.prefill_tokens - before[2] == sum(map(len, prompts))
    # one first token per request comes from prefill, the rest from decode
    assert sched.decode_tokens - before[3] == 3 * (4 - 1)


def test_step_clock_times_steps_up_to_their_wait(engine, monkeypatch):
    """A step that dispatches a prefill chunk and waits on nothing is
    timed with the next step that waits; each waited step is recorded
    once and charged to every phase it carried."""
    sched = engine.scheduler
    recorded = []
    monkeypatch.setattr(engine.straggler, "record",
                        lambda host, dt: recorded.append(dt))
    t_pre, t_dec = sched.prefill_time_s, sched.decode_time_s
    engine.submit([7] * 9, max_new=2, admit=False)
    engine.step()   # chunk 1 of 3: no wait
    engine.step()   # chunk 2 of 3: no wait
    assert recorded == [] and sched.prefill_time_s == t_pre
    engine.step()   # chunk 3, then the first-token wait
    assert len(recorded) == 1
    assert sched.prefill_time_s == pytest.approx(t_pre + recorded[0])
    assert sched.decode_time_s == t_dec
    engine.step()   # decode, waits on the ids
    assert len(recorded) == 2 and recorded[1] > 0
    assert sched.decode_time_s == pytest.approx(t_dec + recorded[1])
    assert not engine.active.any()
