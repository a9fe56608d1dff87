"""Share of its roofline that the row-gather kernel reaches: the least
time for the gathers a step requires on each chip (``work()["kernels"]
["gather_join"]``, from the model's shapes), over the device time of the
kernel's launches on all chips in the trace.

A gather moves bytes and does no arithmetic, so the HBM peak bounds it.
The kernel gathers in launches of a fixed number of rows, so a call is
several events. The share reads nothing unless the window holds a whole
number of launches per gather on every chip (``steps × len(calls) ×
chips × k`` events, k ≥ 1): the kernel is then on the path for every
gather, at any launch size."""

from perfbench import trace_reduce
from perfbench.metrics_common import least_s

LAYER = "kernels"
MOVES = "step_ms"
KERNEL = "gather_join"
#: the kernel's launches, named by its ``pallas_call`` (kernels/gather/gather.py)
PATTERN = r"^%gather_join(\.\d+)? = .*tpu_custom_call"


def read(ctx):
    calls = ctx.work["kernels"].get(KERNEL)
    if ctx.trace is None or ctx.peaks is None or not ctx.steps or not calls:
        return None
    launches = trace_reduce.kernel_calls(ctx.trace, PATTERN)
    if launches == 0 or launches % (ctx.steps * len(calls) * ctx.chips):
        return None
    spent = trace_reduce.kernel_s(ctx.trace, PATTERN)
    return 100.0 * ctx.steps * least_s(ctx, calls) / spent
