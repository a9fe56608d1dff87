"""Time per mini-batch step: the window, from its start to the
completion of its last step, over the steps completed in it. The
mini-batch cells' own ``step_ms``, under a bound of their own: their
host-bound steps spread more from run to run than a kernel-bound one."""

LAYER = None
MOVES = None


def read(ctx):
    return 1e3 * ctx.window_s / ctx.steps
