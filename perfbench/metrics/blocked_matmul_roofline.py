"""Share of its roofline that the blocked matmul kernel reaches: the
least time for the products the steps require of each chip at their
unpadded shapes (2·m·k·n operations, (mk + kn + mn)·4 bytes), over the
device time of the kernel's events on all the chips in the trace."""

from perfbench.metrics_common import roofline_share

LAYER = "kernels"
MOVES = "step_ms"
KERNEL = "blocked_matmul"
#: the kernel's events: the trace names a Pallas call after the jitted
#: wrapper around it (``kernels/*/ops.py``)
PATTERN = r"^%blocked_matmul(\.\d+)? = .*tpu_custom_call"


def read(ctx):
    return roofline_share(ctx, KERNEL, PATTERN)
