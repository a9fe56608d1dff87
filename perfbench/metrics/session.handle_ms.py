"""Host milliseconds per step in ``QueryHandle.step`` outside the
engine: the self time of the program's span ``repro/session.step`` (the
gradient program, the environment off the catalog, the statistics
snapshot, the layout record, the donation marks), less the engine spans
nested in it, over the window's steps."""

from perfbench import program_spans

LAYER = "session front door"
MOVES = "step_ms"
SPAN = "session.step"


def read(ctx):
    return program_spans.self_ms(ctx, SPAN)
