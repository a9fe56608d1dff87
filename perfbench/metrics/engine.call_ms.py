"""Host milliseconds per step in ``Compiled.__call__``: the program's
span ``repro/engine.call`` (the signature check, padding, reshard
accounting and placement, and JAX's dispatch of the jitted step,
``repro/engine.dispatch``), over the window's steps."""

from perfbench import program_spans

LAYER = "engine"
MOVES = "step_ms"
SPAN = "engine.call"


def read(ctx):
    return program_spans.span_ms(ctx, SPAN)
