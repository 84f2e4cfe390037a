"""The int4 packed-linear kernel's share of its roofline over the traced
window.

Numerator: the least time the window's packed linears need, counted by
the configuration's family module (``forward_min_time_s``) from the
model's shapes and each program run's row count as the engine shapes
it, each linear bounded by the larger of int8 operations over the int8
peak and bytes (nibbles, scales, int8 activations in, int32 results
out) over HBM bandwidth: a decode step runs n_slots rows through every
layer and the LM head; a prefill chunk runs the rows the scheduler
counted into prefill over the steps that carried prefill (the continuous
engine runs one chunk program a step, n_slots x chunk rows) through
every layer; the LM head call after prefill, n_slots rows.
Denominator: the summed device time of the kernel's events: the trace
names each by its HLO instruction, ``%int4_matmul.<n> = ...
custom-call(...), custom_call_target="tpu_custom_call"``, after the
jitted wrapper of ``kernels/int4_matmul.py``.  Moves ``out_tok_s``."""

import re

KERNEL = re.compile(r"^%int4_matmul(\.\d+)? = .*tpu_custom_call", re.S)


def read(ctx):
    t, c = ctx.trace, ctx.counters
    if not t or not ctx.peaks:
        return None
    kernel_s = sum(d for name, d in t["ops_s"].items() if KERNEL.search(name))
    if not kernel_s:
        return None
    model, slots, fam = ctx.model, ctx.serve["n_slots"], ctx.family
    forward = lambda rows, head: fam.forward_min_time_s(model, rows,
                                                        ctx.peaks, head)
    runs = t["module_runs"]
    count = lambda key: sum(n for name, n in runs.items() if key in name)
    need = count("_decode_step") * forward(slots, True)
    if count("_prefill_chunk"):
        if not c or not c["prefill_steps"]:
            return None
        need += count("_prefill_chunk") * forward(
            c["prefill_rows"] // c["prefill_steps"], False)
    if count("_lm_head"):
        need += count("_lm_head") * (forward(slots, True)
                                     - forward(slots, False))
    if need <= 0:
        return None
    return 100.0 * need / kernel_s
