"""Fault-injection scenarios: deadline shedding under bursts and page
exhaustion, shed requests freeing lanes/slots/pages, and the CI smoke for
the degradation story — burst → governor degrades → queue drains →
governor recovers → a fresh request is token-identical to a never-
degraded engine.  Scenarios are driven through ``tests/faultinject.py``
(no wall-clock sleeps: expiry is injected by backdating ``deadline_at``
so the production shedding path fires deterministically)."""

import dataclasses

import jax
import pytest

import faultinject as fi
from repro.models import transformer as T
from repro.models.registry import get_config
from repro.serving import (
    ContinuousEngine,
    Engine,
    GovernorConfig,
    ServeConfig,
)

KEY = jax.random.PRNGKey(0)
CFG = dataclasses.replace(get_config("qwen1.5-110b", smoke=True),
                          dtype="float32")
PARAMS = T.init_params(KEY, CFG)


def _engine(quant="native", slots=3, chunk=4, **kw):
    return Engine(CFG, PARAMS, ServeConfig(
        n_slots=slots, max_len=32, prefill_chunk=chunk, quant_mode=quant, **kw
    ))


def _cengine(quant="native", slots=3, chunk=4, **kw):
    kw.setdefault("page_size", 8)
    return ContinuousEngine(CFG, PARAMS, ServeConfig(
        n_slots=slots, max_len=32, prefill_chunk=chunk, quant_mode=quant, **kw
    ))


# ---- deadline shedding ---------------------------------------------------


def test_queued_burst_sheds_on_deadline_continuous():
    """A burst beyond capacity: the queued tail expires and is shed
    without ever touching a lane; survivors finish normally and the page
    pool comes back whole."""
    eng = _cengine(slots=2)
    rids = fi.burst(eng, 6, max_new=4)
    eng.step()  # admits what fits; the rest wait in the queue
    queued = list(eng.scheduler._queue)
    assert queued, "burst was supposed to outrun capacity"
    fi.force_expire(eng, queued)
    eng.step()
    for rid in queued:
        assert eng.scheduler.requests[rid].finish_reason == "deadline"
    fi.drain(eng)
    st = eng.stats()
    assert st["shed"] == len(queued) == st["cancelled"]
    assert st["finished"] == len(rids) - len(queued)
    assert st["free_pages"] == st["n_pages"]
    for rid in set(rids) - set(queued):
        assert eng.scheduler.requests[rid].finish_reason == "length"


def test_shed_running_request_frees_lane_continuous():
    """Expiring a *running* request mid-decode frees its lane and pages
    at the next step boundary; the queued request behind it gets the
    capacity and completes."""
    eng = _cengine(slots=2)
    rids = fi.burst(eng, 3, max_new=8)
    eng.step()
    victim = next(r for r in rids if r not in eng.scheduler._queue)
    # run the victim past its chunked prefill so it is genuinely decoding
    fi.step_until(eng, lambda e: e.scheduler.requests[victim].tokens)
    fi.force_expire(eng, [victim])
    eng.step()
    assert eng.scheduler.requests[victim].finish_reason == "deadline"
    fi.drain(eng)
    st = eng.stats()
    assert st["shed"] == 1
    assert st["free_pages"] == st["n_pages"]
    assert eng.scheduler.requests[rids[2]].finish_reason == "length"
    assert len(eng.scheduler.requests[rids[2]].tokens) == 8


def test_shed_running_request_frees_slot_fixed():
    eng = _engine(slots=2)
    rids = fi.burst(eng, 3, max_new=8)
    eng.step()
    victim = next(r for r in rids if r not in eng.scheduler._queue)
    fi.force_expire(eng, [victim])
    eng.step()
    assert eng.scheduler.requests[victim].finish_reason == "deadline"
    fi.drain(eng)
    assert (eng._slot_rid == -1).all() and not eng.active.any()
    assert eng.stats()["shed"] == 1
    assert eng.scheduler.requests[rids[2]].finish_reason == "length"


def test_page_exhaustion_with_deadlines_drains_clean():
    """A page pool too small for the burst: requests queue on pages, the
    whole backlog is expired, and the engine still drains to an empty,
    fully-freed state — no stuck lanes, no leaked pages."""
    eng = _cengine(slots=4, n_pages=8)  # 64 pooled tokens for the burst
    rids = fi.burst(eng, 8, max_new=8, prompt_len=(6, 10))
    fi.run_steps(eng, 3)
    unfinished = [r for r in rids if not eng.scheduler.requests[r].done]
    assert unfinished
    fi.force_expire(eng, unfinished)
    fi.drain(eng)
    st = eng.stats()
    assert st["free_pages"] == st["n_pages"]
    assert not eng.active.any() and st["running"] == 0
    assert st["shed"] == len(unfinished)
    for rid in rids:
        assert eng.scheduler.requests[rid].done


def test_deadline_ms_engine_default_applies_to_every_submit():
    """ServeConfig.deadline_ms stamps a deadline on requests that don't
    pass their own — the serve-wide SLO knob."""
    eng = _cengine(deadline_ms=60_000.0)
    rid = eng.submit([2, 3, 4], max_new=2)
    req = eng.scheduler.requests[rid]
    assert req.deadline_at is not None
    assert rid in eng.scheduler._deadlined
    # and a per-request override beats the engine default
    rid2 = eng.submit([2, 3], max_new=2, deadline_ms=1e6)
    assert eng.scheduler.requests[rid2].deadline_at > req.deadline_at
    fi.drain(eng)
    assert eng.stats()["shed"] == 0  # generous deadlines: nothing shed


def test_decode_wall_time_feeds_straggler_signal():
    """Every step's wall time, up to its wait on the decode ids, lands in
    the StragglerDetector, so the governor's slow-step signal (and the
    operator's ``median_step_s``) is live after any decoding at all."""
    eng = _cengine(slots=2)
    eng.submit([2, 3, 4], max_new=6)
    fi.drain(eng)
    assert eng.straggler.n_recorded() > 0
    assert eng.straggler.n_recorded() <= eng.straggler.window
    assert eng.stats()["median_step_s"] > 0.0


# ---- the degradation story (CI fast-lane smoke) --------------------------


def test_burst_degrade_recover_token_identity():
    """Burst → the governor swaps to the narrow tier after ``hold_steps``
    deep-queue observations → the queue drains and it recovers one rung
    back to primary → a request served *after* recovery is token-for-
    token identical to a never-degraded engine."""
    gcfg = GovernorConfig(queue_high=3, queue_low=1, hold_steps=2)
    gov = _cengine(quant="dsp_tuned", plan_bits=(8, 8), slots=2,
                   governor=gcfg)
    assert [t.name for t in gov.tiers] == ["primary", "narrow"]

    rids = fi.burst(gov, 8, max_new=3)
    fi.step_until(gov, lambda e: e.active_tier == 1, max_steps=50)
    assert gov.governor.n_swaps == 1
    assert gov.governor.history[-1][1:] == (0, 1)

    fi.drain(gov)
    fi.step_until(gov, lambda e: e.active_tier == 0, max_steps=50)
    assert gov.governor.n_swaps == 2
    assert gov.governor.history[-1][1:] == (1, 0)
    for rid in rids:
        req = gov.scheduler.requests[rid]
        assert req.finish_reason in ("length", "eos")
        assert 1 <= len(req.tokens) <= 3

    prompt = [5, 6, 7, 8]
    rid = gov.submit(prompt, max_new=6)
    fi.drain(gov)
    got = list(gov.scheduler.requests[rid].tokens)

    ref = _cengine(quant="dsp_tuned", plan_bits=(8, 8), slots=2)
    want = ref.generate([prompt], max_new=6)[0]
    assert got == want, "post-recovery serving diverged from primary tier"


@pytest.mark.slow
@pytest.mark.parametrize("make", [_engine, _cengine], ids=["slot", "cont"])
def test_midflight_swap_storm_keeps_serving(make):
    """Repeated manual tier swaps while requests are in flight: every
    request still runs to its full budget and the engine drains clean —
    tier swaps change arithmetic, never request lifecycle."""
    eng = make(quant="dsp_tuned", plan_bits=(8, 8), slots=2,
               governor=GovernorConfig(queue_high=50, emergency_queue_high=99,
                                       hold_steps=2))
    rids = fi.burst(eng, 4, max_new=6)
    for step in range(40):
        if not (eng.active.any() or eng.scheduler.n_queued):
            break
        eng.set_tier(step % 2)
        eng.step()
    assert not (eng.active.any() or eng.scheduler.n_queued)
    for rid in rids:
        req = eng.scheduler.requests[rid]
        assert req.finish_reason in ("length", "eos")
        assert 1 <= len(req.tokens) <= 6
        assert all(0 <= t < CFG.vocab_size for t in req.tokens)
