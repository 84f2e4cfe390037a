"""Milliseconds of device time per decode step over the traced window:
the summed device time of the engine's ``_decode_step`` program, from the
trace's ``XLA Modules`` line, over the number of times it ran.  Moves
``out_tok_s``."""


def read(ctx):
    t = ctx.trace
    if not t:
        return None
    runs = sum(n for name, n in t["module_runs"].items()
               if "_decode_step" in name)
    secs = sum(d for name, d in t["module_s"].items()
               if "_decode_step" in name)
    if runs <= 0 or secs <= 0:
        return None
    return 1e3 * secs / runs
