"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into three lists
of ``(name, start_ns, end_ns)``: the operations of each device (the
``XLA Ops`` line of every ``/device:TPU:<n>`` plane), the programs each
device ran (its ``XLA Modules`` line), and the benchmark's own host spans
(``TraceAnnotation`` events named ``chipbench.*``).  ``reduce`` turns
them into busy and idle time, each operation's summed device self time
(less the operations nested in it), the top kinds of operation, the longest idle gaps (each named by the host span it fell
in) and how many times each program ran and for how long, all inside the
traced window:
from the first benchmark span's start to the last one's end.
"""

from __future__ import annotations

import re
from pathlib import Path

SPAN_PREFIX = "chipbench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(path: str | Path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, modules, spans = {}, {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, e.start_ns, e.end_ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[plane.name] = [
                        (e.name, e.start_ns, e.end_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "modules": modules, "spans": spans}


def _union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(spans, t):
    """The innermost benchmark span holding time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name[len(SPAN_PREFIX):], e - s)
    return best[0] if best else "outside any span"


def module_name(name: str) -> str:
    """A program's name without the run and id suffixes the trace adds."""
    return re.split(r"[(\s]", name, maxsplit=1)[0]


def op_kind(name: str) -> str:
    """An operation's kind: its HLO instruction name (the trace names an
    operation by its whole instruction text) without the id suffix, so
    ``%int4_matmul.53 = s32[...] custom-call(...)`` is ``int4_matmul``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _self_times(ops, lo, hi):
    """Each operation's time inside [lo, hi] less the time of operations
    nested in it (a layer scan's ``while`` holds the layer's operations)."""
    out, stack = [], []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack and e <= stack[-1][1]:
            out[stack[-1][2]][1] -= e - s
        stack.append((s, e, len(out)))
        out.append([name, e - s])
    return out


def reduce(tr: dict, top: int = 10) -> dict:
    spans = tr["spans"]
    if not spans or not tr["devices"]:
        return {}
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    busy, by_op, by_kind, gaps = [], {}, {}, []
    for i, (plane, ops) in enumerate(sorted(tr["devices"].items())):
        merged = _union([(s, e) for _, s, e in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for name, d in _self_times(ops, lo, hi):
            by_op[name] = by_op.get(name, 0.0) + d
            kind = op_kind(name)
            by_kind[kind] = by_kind.get(kind, 0.0) + d
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((_label(spans, (s + e) / 2), e - s))
    counts, module_s = {}, {}
    for evs in tr["modules"].values():
        for name, s, e in evs:
            if lo <= s and e <= hi:
                key = module_name(name)
                counts[key] = counts.get(key, 0) + 1
                module_s[key] = module_s.get(key, 0.0) + (e - s)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / len(busy) * ns,
        "ops_s": {n: t * ns for n, t in by_op.items()},
        "top_ops": [[n, t * ns] for n, t in
                    sorted(by_kind.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, t * ns] for n, t in
                      sorted(gaps, key=lambda g: -g[1])[:top]],
        "module_runs": counts,
        "module_s": {n: t * ns / len(tr["modules"])
                     for n, t in module_s.items()},
    }
