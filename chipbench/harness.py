"""One run of one cell, in this order:

1. refuse to run without the chips the cell asks for (no CPU fallback);
2. turn on JAX's persistent compile cache at a fixed path in the checkout;
3. build the configuration through the user's path — ``get_config``,
   the weights (``weights.py``: made on the device from the seed in one
   jitted call), ``ServeConfig``, the engine — and drop the bf16 tree;
4. warm up by running the cell's own traffic for the mix's
   ``warmup_steps`` (every program the window runs is compiled there);
5. run the closed loop for ``seconds``: each client submits its next
   request as soon as its previous one finishes; every token is stamped
   with the return time of the ``step()`` that emitted it;
6. with ``trace``, profile the first ``TRACE_SECONDS`` of the window and
   read the per-layer metrics from it (the window is lengthened by the
   time the profiler takes to write the trace);
7. check what the window served against the plain reference
   (``reference.py``) on a sample of finished requests drawn from the seed.

The engine calls are wrapped in ``jax.profiler.TraceAnnotation`` spans
(``chipbench.submit``, ``chipbench.step``, ``chipbench.drain_stream``), so
a traced run holds the host's spans and the device's operations on one
clock.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from . import reference, roofline, weights
from .bench import ROOT, Cell
from .traffic import derive, requests

TRACE_SECONDS = 8.0
CACHE_DIR = ROOT / ".jax_cache"


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def devices_for(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devs)}")
    return devs[:chips]


def enable_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


@dataclasses.dataclass
class Rec:
    client: int
    prompt_len: int
    max_new: int
    t_submit: float
    times: list = dataclasses.field(default_factory=list)
    t_done: float | None = None


class Loop:
    """The closed loop of the mix's clients around one engine."""

    def __init__(self, engine, stream):
        from jax.profiler import TraceAnnotation

        self.span = TraceAnnotation
        self.engine = engine
        self.stream = stream
        self.recs: dict[int, Rec] = {}
        self.decode_calls = 0
        self.first_tokens = 0
        self.steps = 0
        self.step_times: list[float] = []

    def submit(self, client: int) -> None:
        req = next(self.stream)
        t = time.perf_counter()
        with self.span("chipbench.submit"):
            rid = self.engine.submit(list(req.prompt), max_new=req.max_new,
                                     admit=False)
        self.recs[rid] = Rec(client, len(req.prompt), req.max_new, t)

    def step(self) -> float:
        sched = self.engine.scheduler
        decoded = sched.decode_tokens
        with self.span("chipbench.step"):
            finished = self.engine.step()
        t = time.perf_counter()
        with self.span("chipbench.drain_stream"):
            for rid, _ in self.engine.drain_stream():
                rec = self.recs[rid]
                self.first_tokens += not rec.times
                rec.times.append(t)
        self.steps += 1
        self.step_times.append(t)
        self.decode_calls += sched.decode_tokens > decoded
        for rid in finished:
            self.recs[rid].t_done = t
            self.submit(self.recs[rid].client)
        return t

    def counters(self) -> dict:
        return {"prefill_tokens": self.engine.scheduler.prefill_tokens}


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def end_to_end(recs, lo: float, hi: float) -> dict:
    inside = lambda t: lo < t <= hi
    n_tok = sum(inside(t) for r in recs for t in r.times)
    n_first = sum(bool(r.times) and inside(r.times[0]) for r in recs)
    itl = [b - a for r in recs for a, b in zip(r.times, r.times[1:])
           if inside(a) and inside(b)]
    return {
        "out_tok_s": n_tok / (hi - lo),
        "itl_p95_ms": (1e3 * float(np.percentile(itl, 95)) if itl else None),
        "n_tokens": n_tok, "n_first": n_first, "n_itl": len(itl),
    }


def model_flops(model: dict, recs, steps, lo: float, hi: float) -> float:
    """Model FLOPs of the real tokens processed in (lo, hi]: each decoded
    position and the LM head once per emitted token, when the token lands
    there; a request's prompt spread evenly over the engine steps from its
    submit to its first token (``steps``: the return time of every step;
    a prefill in chunks runs all through them), and the share of those
    steps inside (lo, hi] counted.  Counting steps, not seconds, keeps a
    compile inside the warm-up from moving prompt work out of the window."""
    head = roofline.lm_head_flops(model)
    steps = np.asarray(steps)
    total = 0.0
    for r in recs:
        if r.times:
            mine = steps[(steps > r.t_submit) & (steps <= r.times[0])]
            if mine.size:
                share = np.count_nonzero((mine > lo) & (mine <= hi))
                total += (roofline.prompt_flops(model, r.prompt_len)
                          * share / mine.size)
        for j, t in enumerate(r.times):
            if lo < t <= hi:
                total += head + (roofline.position_flops(
                    model, r.prompt_len + j - 1) if j else 0)
    return total


def _sample(loop: Loop, seed: int, lo: float, hi: float, check: dict):
    """``check['requests']`` finished requests to check: the longest, then
    others drawn from the seed.  (A long answer from random weights soon
    repeats one token by a wide margin; the early tokens of many requests
    are what a lower precision gets wrong, so the sample is counted in
    requests, not tokens.)"""
    done = [rid for rid, r in loop.recs.items()
            if r.t_done is not None and lo < r.t_done <= hi]
    if not done:
        return []
    size = lambda rid: loop.recs[rid].prompt_len + loop.recs[rid].max_new
    longest = max(done, key=lambda rid: (size(rid), rid))
    rest = [rid for rid in sorted(done) if rid != longest]
    rng = np.random.default_rng(derive(seed, "sample"))
    order = [longest] + [rest[int(i)] for i in rng.permutation(len(rest))]
    return order[:check["requests"]]


def _free() -> None:
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def build(cell: Cell, seed: int):
    """The engine of the cell's configuration, built as a user builds one;
    returns ``(engine, weight_seed)``."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    from repro.models.registry import get_config
    from repro.serving import ContinuousEngine, Engine, ServeConfig

    conf = cell.config
    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["model"])
    wseed = derive(seed, "weights")
    params = weights.params(reference.Model.from_config(conf["model"]),
                            jax.random.PRNGKey(wseed))
    scfg = ServeConfig(**conf["serve"], seed=wseed)
    engine_cls = {"slot": Engine, "continuous": ContinuousEngine}[
        conf["engine"]]
    engine = engine_cls(cfg, params, scfg)
    jax.block_until_ready(engine.params)
    del params  # the engine serves its own packed copy
    gc.collect()
    return engine, wseed


def judge(checks: dict) -> bool:
    """The one predicate that decides ``correct``: the served tokens lie
    within the limit of the reference's best, enough of them were read,
    and every request served the length it asked for (no gap read: not
    correct)."""
    gap = checks["logit_gap"]["value"]
    return (gap is not None and gap <= checks["logit_gap"]["limit"]
            and checks["tokens_checked"]["value"]
            >= checks["tokens_checked"]["limit"]
            and checks["wrong_lengths"]["value"] == 0)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t0: float, controls: tuple[str, ...] = ()) -> dict:
    """One run; returns the result line as a dict.  Each of ``controls``
    (names of ``reference.CONTROLS``) is also read in the program's place
    and judged by the same predicate, under ``controls`` in the result:
    for calibration only."""
    devs = devices_for(cell.chips)
    kind = devs[0].device_kind
    peak_table = roofline.peaks(kind)
    import jax

    enable_cache()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, dur, **kw: compiles.append(time.perf_counter())
        if "backend_compile" in event else None)

    conf, mix = cell.config, cell.traffic
    model = conf["model"]
    t_build = time.perf_counter()
    engine, wseed = build(cell, seed)
    t_warm = time.perf_counter()
    stream = requests(mix, seed, model["vocab_size"])
    loop = Loop(engine, stream)
    for c in range(int(mix["clients"])):
        loop.submit(c)
    while (loop.steps < mix["warmup_steps"] or not loop.decode_calls
           or not loop.first_tokens):
        loop.step()
    log(f"set-up: {t_build - t0:.3f}s to the device, build "
        f"{t_warm - t_build:.3f}s, warm-up {time.perf_counter() - t_warm:.3f}s"
        f" ({loop.steps} steps, {loop.decode_calls} decode calls, "
        f"{sum(r.t_done is not None for r in loop.recs.values())} "
        "requests finished)")

    before = loop.counters()
    t_open = time.perf_counter()
    setup_s = t_open - t0
    tr, t_trace, traced = None, None, None
    if trace:
        tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tmp)
    t, deadline = t_open, t_open + seconds
    while t < deadline:
        t = loop.step()
        if trace and t_trace is None and t - t_open >= min(TRACE_SECONDS,
                                                           seconds):
            t_trace = t
            traced = _delta(before, loop.counters())
            jax.profiler.stop_trace()
            # writing the trace stalls the loop: serve as long as an
            # untraced run does, so the check reads as many requests
            deadline += time.perf_counter() - t
    if trace and t_trace is None:
        t_trace = t
        traced = _delta(before, loop.counters())
        jax.profiler.stop_trace()
    t_close = t
    n_compiles = sum(t_open < c <= t_close for c in compiles)
    recs = list(loop.recs.values())
    # the window is exactly ``seconds`` long: tokens of the step that runs
    # past its end are outside it
    e2e = end_to_end(recs, t_open, t_open + seconds)
    log(f"window {seconds:.3f}s (last step ended at "
        f"{t_close - t_open:.3f}s): {loop.steps} steps in all, "
        f"{e2e['n_tokens']} tokens, {e2e['n_first']} first tokens, "
        f"{e2e['n_itl']} gaps, {n_compiles} compiles inside the window")

    # what the window holds: the bytes in use on the fullest chip as the
    # window closes (the process's peak comes from the build, where the
    # bf16 tree sits beside its packed copy, and is only logged)
    stats = [d.memory_stats() or {} for d in devs]
    in_use = max(s.get("bytes_in_use", 0) for s in stats)
    limit = min(s.get("bytes_limit", 0) for s in stats) or None
    log(f"memory: {in_use} bytes in use as the window closes, process "
        f"peak {max(s.get('peak_bytes_in_use', 0) for s in stats)}, "
        f"limit {limit}")
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(in_use)}

    metrics, breakdown = {}, None
    if trace:
        from . import trace as tracemod

        paths = sorted(Path(tmp).rglob("*.xplane.pb"))
        red = tracemod.reduce(tracemod.load(paths[-1])) if paths else {}
        shutil.rmtree(tmp, ignore_errors=True)
        ctx = SimpleNamespace(
            model=model, serve=conf["serve"], engine=conf["engine"],
            window_s=t_trace - t_open, counters=traced,
            flops=model_flops(model, recs, loop.step_times, t_open, t_trace),
            peaks=peak_table,
            memory={"in_use": in_use, "limit": limit}, trace=red or None,
        )
        for m in cell.per_layer:
            value = cell.readers[m.name](ctx)
            if value is not None:
                metrics[m.name] = {"value": float(value), "unit": m.unit}
        if red:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["top_ops"],
                         "idle_gaps": red["idle_gaps"]}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in cell.end_to_end:
            if values.get(m.name) is not None:
                metrics[m.name] = {"value": float(values[m.name]),
                                   "unit": m.unit}

    # ---- correct: the window's served tokens against the reference ----
    check = mix["check"]
    picked = _sample(loop, seed, t_open, t_close, check)
    requests_ = engine.scheduler.requests
    seqs = [(list(requests_[rid].prompt), list(requests_[rid].tokens))
            for rid in picked]
    wrong_len = sum(len(s) != loop.recs[rid].max_new
                    for rid, (_, s) in zip(picked, seqs))
    attempted = sum(r.t_submit <= t_close and
                    (r.t_done is None or r.t_done > t_open) for r in recs)
    failed = sum(r.finish_reason in ("cancelled", "deadline")
                 for r in requests_.values())
    del engine, loop, requests_
    _free()
    t_ref = time.perf_counter()
    limit = conf["check"]["logit_gap"]
    checks = {"logit_gap": {"value": None, "limit": limit},
              "tokens_checked": {"value": sum(len(s) for _, s in seqs),
                                 "limit": check["min_tokens"]},
              "wrong_lengths": {"value": wrong_len, "limit": 0}}
    verdicts = {}
    for control in (None,) + tuple(controls) if seqs else ():
        with jax.default_matmul_precision("highest"):
            got = reference.gaps(
                model, wseed, seqs, n_rows=check["requests"],
                seq_len=mix["prompt"]["max"] + mix["output"]["max"],
                control=control)
        if control is None:
            checks["logit_gap"]["value"] = float(got["gap"].max())
            log(f"reference margins: median {np.median(got['margin']):.4f}, "
                f"{int((got['margin'] < 0.05).sum())} of {got['margin'].size}"
                " under 0.05; distinct served tokens per request "
                f"{[len(set(s)) for _, s in seqs]}")
        else:
            # the control in the program's place, judged as the program is
            gap = {"value": float(got["control_gap"].max()), "limit": limit}
            verdicts[control] = judge(dict(checks, logit_gap=gap))
            checks[f"control_{control}_logit_gap"] = gap
    log(f"reference over {len(seqs)} requests in "
        f"{time.perf_counter() - t_ref:.3f}s")
    ok = judge(checks)
    for name, c in checks.items():
        rel = ">=" if name == "tokens_checked" else "<="
        log(f"check {name} {c['value']} {rel} {c['limit']}")
    for control, verdict in verdicts.items():
        log(f"control {control}: correct {verdict}")
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if verdicts:
        result["controls"] = verdicts
    result["checks"] = checks
    return result
