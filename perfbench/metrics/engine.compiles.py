"""XLA executables compiled, or loaded from the compile cache, inside
the window. Every shape is warmed up in set-up, so it should read 0."""

LAYER = "engine"
MOVES = "step_ms"


def read(ctx):
    return ctx.compiles
