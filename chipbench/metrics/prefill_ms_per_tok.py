"""Milliseconds of device time per prompt token over the traced window:
the summed device time of the engine's ``_prefill_chunk`` program, from
the trace's ``XLA Modules`` line, over the prompt tokens the scheduler
counted into prefill across the traced window (``Scheduler.
note_prefill``).  On the continuous engine every step that carries a
prefill chunk is longer by that chunk, so this moves ``itl_p95_ms``."""


def read(ctx):
    t, c = ctx.trace, ctx.counters
    if not t or not c or c["prefill_tokens"] <= 0:
        return None
    secs = sum(d for name, d in t["module_s"].items()
               if "_prefill_chunk" in name)
    if secs <= 0:
        return None
    return 1e3 * secs / c["prefill_tokens"]
