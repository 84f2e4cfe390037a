"""Percent of the chip's memory that the window holds: ``memory_stats()``
``bytes_in_use`` as the window closes (packed weights, the KV cache and
whatever else is live between steps) over ``bytes_limit``.  Temporaries
inside a step are not seen, nor is the engine build's peak, where the
bf16 tree sits beside its packed copy.  Moves ``out_tok_s`` (the memory
left over bounds the batch and the context)."""


def read(ctx):
    m = ctx.memory
    if not m or not m.get("limit") or not m.get("in_use"):
        return None
    return 100.0 * m["in_use"] / m["limit"]
