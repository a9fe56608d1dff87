"""Set-up: from the start of the process to the end of the first steps
(start-up, data, compiles or compile-cache loads, the first steps)."""

LAYER = None
MOVES = None


def read(ctx):
    return ctx.setup_s
