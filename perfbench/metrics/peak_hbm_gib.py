"""Peak device memory of the run, in GiB, on the fullest chip: the
runtime's peak of live arrays (``peak_bytes_in_use``) plus the largest
temporaries that XLA's buffer assignment gives a program the window
runs, read after the window and before the reference runs. The TPU
runtime's own peak leaves out an execution's temporaries, so a copy
added inside a step shows only in the second term."""

LAYER = None
MOVES = None


def read(ctx):
    if ctx.memory_peak_bytes is None:
        return None
    return ctx.memory_peak_bytes / 2**30
