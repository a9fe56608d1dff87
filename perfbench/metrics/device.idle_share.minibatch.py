"""``device.idle_share`` in the cells whose step time is ``minibatch_step_ms``."""

from perfbench.metrics_common import read_as

LAYER = "device"
MOVES = "minibatch_step_ms"
read = read_as("device.idle_share")
