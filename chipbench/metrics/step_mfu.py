"""The whole step's share of the chip's int8 peak over the traced window:
model FLOPs of the real tokens processed (2 per matmul parameter per
position, attention over the positions actually attended, the LM head
once per emitted token, a prompt spread evenly over the steps from its
submit to its first token; padded rows do not count), over the window's
seconds, over the int8 peak of the peak table (``int4_packed`` feeds the
int8 MXU).  Moves ``out_tok_s``."""


def read(ctx):
    if not ctx.peaks or ctx.window_s <= 0 or ctx.flops <= 0:
        return None
    return 100.0 * ctx.flops / ctx.window_s / ctx.peaks["int8_ops"]
