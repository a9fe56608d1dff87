"""Host milliseconds per step in the session's executable lookup: the
program's span ``repro/engine.lookup`` (the engine, ``lower`` and
``compile_auto`` caches; a miss would nest ``repro/engine.lower`` and
``repro/engine.plan`` inside it), over the window's steps."""

from perfbench import program_spans

LAYER = "engine"
MOVES = "step_ms"
SPAN = "engine.lookup"


def read(ctx):
    return program_spans.span_ms(ctx, SPAN)
