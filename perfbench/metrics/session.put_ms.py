"""Host milliseconds per step in ``Database.put``: the program's span
``repro/session.put`` (the array wrap, the host-tier check, the catalog
entry and its statistics refresh, ``repro/session.stats``), summed over
the window and divided by its steps."""

from perfbench import program_spans

LAYER = "session front door"
MOVES = "step_ms"
SPAN = "session.put"


def read(ctx):
    return program_spans.span_ms(ctx, SPAN)
