"""Share of its roofline that the fused join-aggregate reaches on the
chips of a sharded cell: the least time for the calls a step requires
on each chip (``work()["kernels"]["join_agg"]``: each convolution's
gather, ⊗ and Σ over the chip's own edges, from the model's shapes),
over the device time of those calls on all chips in the trace.

A call that does not fit one block runs as one loop over blocks of
edges (``repro.core.compiler._coo_join_agg``), named ``join_agg`` in
the program's metadata, which the trace's operation text leaves out.
The trace shows the loop as one ``%while`` operation whose span holds
the block's row-gather launches (``%gather_join``) and Σ launches
(``%segment_sum``); the gathers' own launch loops hold no Σ. A call's
device time is the span of its loop, the outermost such operation. The
share reads nothing unless the window holds exactly ``steps ×
len(calls) × chips`` of them: the calls are then the counted ones."""

import bisect
import re

from perfbench.metrics_common import least_s

LAYER = "kernels"
MOVES = "step_ms"
KERNEL = "join_agg"
LOOP = re.compile(r"^%while(\.\d+)? = ")
GATHER = re.compile(r"^%gather_join(\.\d+)? = .*tpu_custom_call")
SIGMA = re.compile(r"^%segment_sum(\.\d+)? = .*tpu_custom_call")


def loops(ops) -> list:
    """``(start, end)`` of the outermost loops among ``ops`` (one chip's)
    whose span holds both a row-gather and a Σ launch."""
    starts = {rx: sorted(op.start for op in ops if rx.search(op.text))
              for rx in (GATHER, SIGMA)}

    def holds(rx, s, e):
        i = bisect.bisect_left(starts[rx], s)
        return i < len(starts[rx]) and starts[rx][i] < e

    found = sorted((op.start, op.end) for op in ops if LOOP.search(op.text)
                   and holds(GATHER, op.start, op.end) and holds(SIGMA, op.start, op.end))
    outer = []
    for s, e in found:
        if outer and s < outer[-1][1]:
            outer[-1] = (outer[-1][0], max(outer[-1][1], e))
        else:
            outer.append((s, e))
    return outer


def read(ctx):
    calls = ctx.work["kernels"].get(KERNEL)
    if ctx.trace is None or ctx.peaks is None or not ctx.steps or not calls:
        return None
    w0, w1 = ctx.trace.window
    spans = [span for ops in ctx.trace.devices.values() for span in loops(ops)]
    if len(spans) != ctx.steps * len(calls) * ctx.chips:
        return None
    spent = 1e-9 * sum(min(e, w1) - max(s, w0) for s, e in spans)
    return 100.0 * ctx.steps * least_s(ctx, calls) / spent
