"""The int4 packed-linear kernel's share of its roofline over the traced
window.

Numerator: the least time the window's packed linears need, counted by
``chipbench.roofline`` from the model's shapes and each program run's row
count as the engine shapes it (a decode step: n_slots rows through every
layer and the LM head; a prefill chunk: n_slots x chunk rows through
every layer; the LM head call after prefill: n_slots rows), each linear
bounded by the larger of int8 operations over the int8 peak and bytes
(nibbles, scales, int8 activations in, int32 results out) over HBM
bandwidth.  Denominator: the summed device time of the kernel's events:
the trace names each by its HLO instruction, ``%int4_matmul.<n> = ...
custom-call(...), custom_call_target="tpu_custom_call"``, after the
jitted wrapper of ``kernels/int4_matmul.py``.  Moves ``out_tok_s``."""

import re

from chipbench import roofline

KERNEL = re.compile(r"^%int4_matmul(\.\d+)? = .*tpu_custom_call", re.S)


def read(ctx):
    t = ctx.trace
    if not t or not ctx.peaks:
        return None
    kernel_s = sum(d for name, d in t["ops_s"].items() if KERNEL.search(name))
    if not kernel_s:
        return None
    model, serve = ctx.model, ctx.serve
    slots = serve["n_slots"]
    chunk = 1 if model.get("sliding_window") else serve["prefill_chunk"]
    runs = t["module_runs"]
    count = lambda key: sum(n for name, n in runs.items() if key in name)
    need = (count("_decode_step")
            * roofline.forward_min_time_s(model, slots, ctx.peaks, True)
            + count("_prefill_chunk")
            * roofline.forward_min_time_s(model, slots * chunk, ctx.peaks,
                                          False)
            + count("_lm_head")
            * roofline.forward_min_time_s(dict(model, n_layers=0), slots,
                                          ctx.peaks, True))
    if need <= 0:
        return None
    return 100.0 * need / kernel_s
