"""Host milliseconds per step inside the program's session front door:
the benchmark's spans around ``db.put`` (``put``) and around the step
call (``step``: ``QueryHandle.step``, or the jitted step of the
relational ops) up to its return, over the window's steps."""

LAYER = "session front door"
MOVES = "step_ms"
SPANS = ("put", "step")


def read(ctx):
    if ctx.trace is None:
        return None
    w0, w1 = ctx.trace.window
    ns = sum(e - s for name, s, e in ctx.trace.spans
             if name in SPANS and w0 <= s and e <= w1)
    return 1e-6 * ns / ctx.steps
