"""The whole step's share of its chips' bf16 peak: the operations the
forward and backward passes require on all the chips, counted from the
shapes (nothing recomputed counts), times the steps of the traced
window, over the window and the cell's chips times one chip's peak, in
%."""

LAYER = "model step"
MOVES = "step_ms"


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    flops = ctx.work["flops"] * ctx.steps
    peak = ctx.chips * ctx.peaks["bf16_flops_per_s"]
    return 100.0 * flops / (ctx.trace.window_s * peak)
