"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, in %."""

from perfbench import trace_reduce

LAYER = "device"
MOVES = "step_ms"


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - trace_reduce.busy_s(ctx.trace) / ctx.trace.window_s)
