"""``blocked_matmul_roofline`` in the cells whose step time is ``minibatch_step_ms``."""

from perfbench.metrics_common import read_as

LAYER = "kernels"
MOVES = "minibatch_step_ms"
read = read_as("blocked_matmul_roofline")
