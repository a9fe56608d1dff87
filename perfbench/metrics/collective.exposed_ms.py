"""Exposed collective time per step: on each chip, the time in which a
collective operation (an all-reduce, reduce-scatter, all-gather,
collective-permute or all-to-all, or the start or done of one) runs
while no other operation does, over the traced window; the mean over
the chips that ran any operation, per step, in ms. A collective that
overlaps other work adds nothing; a trace without collectives reads
nothing. A TPU runs a reduce-scatter as a fusion of its own
(``%fusion.N = ... kind=kCustom, calls=%all-reduce-scatter``), which
counts as one. A chip that reaches a collective before the others
waits inside it, so the time includes that wait."""

import re

from perfbench import trace_reduce

LAYER = "collectives"
MOVES = "step_ms"
COLLECTIVE = re.compile(
    r"^%(all-reduce|reduce-scatter|all-gather|collective-permute|all-to-all)"
    r"(-start|-done)?(\.\d+)? = "
    r"|^[^\n]*, calls=%all-reduce-scatter(\.\d+)?(\n|$)")


def exposed_ns(ops, window) -> int:
    """Nanoseconds of ``window`` covered by collective ``ops`` and by no
    other operation, on one chip."""
    w0, w1 = window
    clip = [(op, max(op.start, w0), min(op.end, w1)) for op in ops]
    coll = trace_reduce.union((s, e) for op, s, e in clip
                              if e > s and COLLECTIVE.search(op.text))
    other = trace_reduce.union((s, e) for op, s, e in clip
                               if e > s and not COLLECTIVE.search(op.text))
    total, j = 0, 0
    for s, e in coll:
        covered = 0
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            covered += min(e, other[k][1]) - max(s, other[k][0])
            k += 1
        total += (e - s) - covered
    return total


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or not ctx.steps:
        return None
    if not any(COLLECTIVE.search(op.text)
               for ops in ctx.trace.devices.values() for op in ops):
        return None
    per_chip = [exposed_ns(ops, ctx.trace.window) for ops in ctx.trace.devices.values()]
    return 1e-6 * sum(per_chip) / len(per_chip) / ctx.steps
