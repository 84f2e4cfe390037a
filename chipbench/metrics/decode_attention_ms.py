"""Milliseconds of device time per decode step in paged attention over
the traced window: the device self time of the operations of the
engine's ``_decode_step`` program whose scope path holds the named scope
``paged_attention`` (``kv_write``, ``kv_gather``, ``scores``; joined from
the compiled program's metadata by ``trace.reduce``), over the number of
times the program ran.  Moves ``out_tok_s``."""


def read(ctx):
    t = ctx.trace
    if not t or not t.get("scopes_s"):
        return None
    runs = sum(n for name, n in t["module_runs"].items()
               if "_decode_step" in name)
    secs = sum(d for prog, scopes in t["scopes_s"].items()
               if "_decode_step" in prog
               for path, d in scopes.items()
               if "paged_attention" in path.split("/"))
    if runs <= 0 or secs <= 0:
        return None
    return 1e3 * secs / runs
