"""The program's own host spans in a traced run, and the readings of
them that the host metrics report.

The program records ``jax.profiler.TraceAnnotation`` spans named
``repro/<layer>.<stage>`` (``src/repro/core/spans.py``) on the same host
plane, and the same clock, as the benchmark's ``bench/*`` spans.
``trace_reduce.reduce_profile`` keeps the benchmark's alone. Importing
this module extends the reduction (a metric reader imports it, so it is
in place before the traced window is reduced):

* ``reduce_profile`` also keeps the program's spans, as ``(name, start,
  end, line)`` with the prefix taken off, on ``Reduced.program``: empty
  where the program records none;
* ``gaps`` labels an idle stretch ``<benchmark span>/<program span>``,
  the innermost program span open at its middle, where there is one,
  and as before elsewhere.

A trace without program spans therefore reduces exactly as before.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from perfbench import trace_reduce

PREFIX = "repro/"

Span = Tuple[str, int, int, int]  # name without the prefix, start, end, line

_bench_reduce_profile = trace_reduce.reduce_profile
_bench_gaps = trace_reduce.gaps


def spans_of(profile) -> List[Span]:
    """The program's spans in a ``ProfileData``, by start."""
    out: List[Span] = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name[len(PREFIX):], int(ev.start_ns),
                                int(ev.end_ns), line_no))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def reduce_profile(profile) -> trace_reduce.Reduced:
    """``trace_reduce.reduce_profile``, keeping the program's spans too."""
    red = _bench_reduce_profile(profile)
    red.program = spans_of(profile)
    return red


def program(red) -> List[Span]:
    """The program's spans of a reduction (none where it was made
    without this module, or from a program that records none)."""
    return getattr(red, "program", None) or []


def gaps(red) -> List[Tuple[str, int, int]]:
    """``trace_reduce.gaps``, each label followed by the innermost program
    span open at the gap's middle, where one is."""
    out = _bench_gaps(red)
    spans = program(red)
    if not spans or not out:
        return out
    inner = trace_reduce._labels([(n, s, e) for n, s, e, _ in spans],
                                 [(s + e) // 2 for _, s, e in out])
    return [(lab if sub == "none" else f"{lab}/{sub}", s, e)
            for (lab, s, e), sub in zip(out, inner)]


trace_reduce.reduce_profile = reduce_profile
trace_reduce.gaps = gaps


def _in_window(red, spans: List[Span]) -> List[Span]:
    w0, w1 = red.window
    return [sp for sp in spans if w0 <= sp[1] and sp[2] <= w1]


def total_ns(red, name: str) -> Optional[int]:
    """Summed nanoseconds of the spans named ``name`` that lie inside
    the window; None where the window holds none."""
    mine = [e - s for n, s, e, _ in _in_window(red, program(red)) if n == name]
    return sum(mine) if mine else None


def self_ns(red, name: str) -> Optional[int]:
    """Summed self time of the spans named ``name`` inside the window:
    each one's duration less the union of the spans nested in it (those
    on its line that start and end inside it); None where the window
    holds none."""
    by_line: Dict[int, List[Span]] = {}
    for sp in program(red):
        by_line.setdefault(sp[3], []).append(sp)
    total, found = 0, False
    for spans in by_line.values():
        starts = [sp[1] for sp in spans]
        for sp in _in_window(red, spans):
            n, s, e, _ = sp
            if n != name:
                continue
            found = True
            inner = []
            for k in range(bisect.bisect_left(starts, s), len(spans)):
                other = spans[k]
                if other[1] >= e:
                    break
                if other is not sp and other[2] <= e:
                    inner.append(other[1:3])
            total += e - s - sum(b - a for a, b in trace_reduce.union(inner))
    return total if found else None


def per_step_ms(ctx, ns: Optional[int]) -> Optional[float]:
    """``ns`` over the window's steps, in milliseconds."""
    if ns is None or not ctx.steps:
        return None
    return 1e-6 * ns / ctx.steps


def span_ms(ctx, name: str) -> Optional[float]:
    """Milliseconds per step in the program span ``name``."""
    if ctx.trace is None:
        return None
    return per_step_ms(ctx, total_ns(ctx.trace, name))


def self_ms(ctx, name: str) -> Optional[float]:
    """Milliseconds per step in the program span ``name`` outside the
    program spans nested in it."""
    if ctx.trace is None:
        return None
    return per_step_ms(ctx, self_ns(ctx.trace, name))
