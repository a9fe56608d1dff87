"""``session.host_ms`` in the cells whose step time is ``minibatch_step_ms``."""

from perfbench.metrics_common import read_as

LAYER = "session front door"
MOVES = "minibatch_step_ms"
read = read_as("session.host_ms")
