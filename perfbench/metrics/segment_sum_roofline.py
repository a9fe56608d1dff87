"""Share of its roofline that the Σ kernel reaches: the least time the
chips could take for the Σ calls the steps require of each (the larger
of their operations over the bf16 peak and their bytes over the HBM
peak, counted from the model's shapes), over the device time of the
kernel's events on all the chips in the trace."""

from perfbench.metrics_common import roofline_share

LAYER = "kernels"
MOVES = "step_ms"
KERNEL = "segment_sum"
#: the kernel's events: the trace names a Pallas call after the jitted
#: wrapper around it (``kernels/*/ops.py``)
PATTERN = r"^%segment_sum(\.\d+)? = .*tpu_custom_call"


def read(ctx):
    return roofline_share(ctx, KERNEL, PATTERN)
