"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into three lists
of ``(name, start_ns, end_ns)``: the operations of each device (the
``XLA Ops`` line of every ``/device:TPU:<n>`` plane), the programs each
device ran (its ``XLA Modules`` line), and the benchmark's own host spans
(``TraceAnnotation`` events named ``chipbench.*``).  ``reduce`` turns
them into busy and idle time, each operation's summed device self time
(less the operations nested in it), the top kinds of operation, the
longest idle gaps (each named by the host span it fell in) and how many
times each program ran and for how long, all inside the traced window:
from the first benchmark span's start to the last one's end.

Given the optimized HLO text of the programs (``hlo``: module name ->
text), ``reduce`` also gives device time to named scopes: the trace names
an operation by its HLO instruction and carries no scope, so each
operation of a program run is joined by its instruction name to that
instruction's ``op_name`` metadata in the program's text, and the
``jax.named_scope`` names in it (``paged_attention/kv_gather``, ``mlp``)
are its scope path.  (The profile's own metadata plane is not read:
``ProfileData`` shows it with no events.)
"""

from __future__ import annotations

import bisect
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


def instruction(name: str) -> str:
    """An operation's HLO instruction name: ``%fusion.12 = ...`` is
    ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_kind(name: str) -> str:
    """An operation's kind: its HLO instruction name (the trace names an
    operation by its whole instruction text) without the id suffix, so
    ``%int4_matmul.53 = s32[...] custom-call(...)`` is ``int4_matmul``."""
    return re.sub(r"\.\d+$", "", instruction(name))


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


INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s")
OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
SCOPE = re.compile(r"[A-Za-z_][\w.\-]*")
# the names JAX itself puts on the path: control flow and call wrappers
# (a transformation is ``name(...)`` and no scope name has brackets)
STRUCTURE = {"while", "body", "cond", "closed_call", "core_call",
             "checkpoint", "remat", "scan", "pjit", "shard_map",
             "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr"}
BRANCH = re.compile(r"branch_\d+_fun")


def op_names(text: str) -> dict[str, str]:
    """Instruction name -> its ``op_name`` metadata ("" without one), for
    every instruction of an HLO module's text."""
    out = {}
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            meta = OP_NAME.search(line)
            out[m.group(1)] = meta.group(1) if meta else ""
    return out


def scope_path(op_name: str) -> str:
    """The named scopes of an ``op_name``, outermost first:
    ``jit(_decode_step)/while/body/closed_call/checkpoint/paged_attention/
    kv_gather/gather`` is ``paged_attention/kv_gather``; "" for none.  The
    last part names the primitive, not a scope."""
    return "/".join(
        p for p in op_name.split("/")[:-1]
        if SCOPE.fullmatch(p) and p not in STRUCTURE
        and not BRANCH.fullmatch(p))


def _scopes(tr: dict, lo: float, hi: float, hlo: dict[str, str]):
    """Device self time of each program's runs inside [lo, hi] by scope
    path, and the share of that time whose instructions were found in the
    program's HLO text."""
    names = {prog: op_names(text) for prog, text in hlo.items()}
    by_scope, joined, total = {}, {}, {}
    for plane, runs in tr["modules"].items():
        ops = sorted(tr["devices"].get(plane, []), key=lambda o: o[1])
        starts = [s for _, s, _ in ops]
        for run, s0, e0 in runs:
            if not (lo <= s0 and e0 <= hi):
                continue
            prog = module_name(run)
            meta = names.get(prog, {})
            scopes = by_scope.setdefault(prog, {})
            # an operation belongs to the run it starts in
            inside = ops[bisect.bisect_left(starts, s0):
                         bisect.bisect_right(starts, e0)]
            for name, d in _self_times(inside, s0, e0):
                total[prog] = total.get(prog, 0.0) + d
                inst = instruction(name)
                if inst in meta:
                    joined[prog] = joined.get(prog, 0.0) + d
                    path = scope_path(meta[inst])
                    scopes[path] = scopes.get(path, 0.0) + d
    n = len(tr["modules"])
    return ({prog: {p: t * 1e-9 / n for p, t in sc.items()}
             for prog, sc in by_scope.items()},
            {prog: joined.get(prog, 0.0) / t for prog, t in total.items()
             if t > 0})


def reduce(tr: dict, top: int = 10, hlo: dict[str, str] | None = None) -> dict:
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
    scopes_s, scope_matched = _scopes(tr, lo, hi, hlo or {})
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
        "scopes_s": scopes_s,
        "scope_matched": scope_matched,
    }
