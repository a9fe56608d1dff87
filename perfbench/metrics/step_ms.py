"""Time per gradient step: the window, from its start to the completion
of its last step, over the steps completed in it."""

LAYER = None
MOVES = None


def read(ctx):
    return 1e3 * ctx.window_s / ctx.steps
