"""Share of its roofline that the row-gather kernel reaches: the least
time for the gathers a GCN step requires, over the device time of the
kernel's launches in the trace.

A step gathers three times, one row per edge (E edges, a self loop per
node included, unpadded): the features (D = features) and the hidden
layer (D = hidden) forward, and the second convolution's output
gradient (D = hidden) backward. Each moves E·D·4 bytes read, E·D·4
written and E·4 of row ids, and does no arithmetic, so the HBM peak
bounds it.

The kernel gathers in launches of a fixed number of rows, so a call is
several events. The share reads nothing unless the window holds a whole
number of launches per gather (``steps × 3 × k`` events, k ≥ 1): the
kernel is then on the path for every gather, at any launch size."""

from perfbench import trace_reduce
from perfbench.metrics_common import roofline_s

LAYER = "kernels"
MOVES = "step_ms"
#: the kernel's launches, named by its ``pallas_call`` (kernels/gather/gather.py)
PATTERN = r"^%gather_join(\.\d+)? = .*tpu_custom_call"


def gathers(cfg: dict) -> list:
    """``(flops, bytes)`` of each gather of one step."""
    n = int(cfg["nodes"])
    e = int(cfg["edges"]) + n
    return [(0, 4 * (2 * e * d + e))
            for d in (int(cfg["features"]), int(cfg["hidden"]), int(cfg["hidden"]))]


def read(ctx):
    if ctx.trace is None or ctx.peaks is None or not ctx.steps:
        return None
    calls = gathers(ctx.cell.cfg)
    launches = trace_reduce.kernel_calls(ctx.trace, PATTERN)
    if launches == 0 or launches % (ctx.steps * len(calls)):
        return None
    spent = trace_reduce.kernel_s(ctx.trace, PATTERN)
    return 100.0 * ctx.steps * roofline_s(calls, ctx.peaks) / spent
