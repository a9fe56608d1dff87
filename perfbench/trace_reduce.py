"""Reduction from a profiler trace to the benchmark's device numbers.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. A device plane (``/device:TPU:<n>``) holds the operations
that ran on that chip on its ``XLA Ops`` line; the host plane holds the
benchmark's own spans (``jax.profiler.TraceAnnotation`` named
``bench/<what>``, see ``harness.Spans``), on the same clock. From them:

* the window: the span ``bench/window``, around the measured steps;
* busy time: the union of a device's operation intervals inside the
  window, averaged over the devices that ran any;
* a kernel's time: the summed durations of the operations whose name or
  whose text stats match the kernel's pattern;
* idle gaps: the stretches of the window in which a device ran nothing,
  each labelled by the innermost benchmark span open at its middle
  (``none`` when the host was outside every span).
"""

from __future__ import annotations

import dataclasses
import gzip
import re
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench/"
WINDOW = "window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Op:
    name: str
    start: int  # ns
    end: int    # ns
    text: str   # the name and every string stat, for kernel patterns


@dataclasses.dataclass
class Reduced:
    window: Tuple[int, int]
    devices: Dict[str, List[Op]]
    spans: List[Tuple[str, int, int]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _text(ev) -> str:
    parts = [ev.name]
    for _, value in ev.stats:
        if isinstance(value, str):
            parts.append(value)
    return "\n".join(parts)


def reduce_profile(profile) -> Reduced:
    """Pick the window, the device operations inside it and the
    benchmark's spans out of a ``ProfileData``."""
    spans: List[Tuple[str, int, int]] = []
    devices: Dict[str, List[Op]] = {}
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((
                            ev.name[len(SPAN_PREFIX):],
                            int(ev.start_ns), int(ev.end_ns),
                        ))
        elif DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(Op(ev.name, int(ev.start_ns), int(ev.end_ns),
                                  _text(ev)))
            devices[plane.name] = ops
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} '{SPAN_PREFIX}{WINDOW}' spans in the trace")
    w0, w1 = windows[0]
    devices = {
        d: [op for op in ops if op.end > w0 and op.start < w1]
        for d, ops in devices.items()
    }
    return Reduced((w0, w1), {d: ops for d, ops in devices.items() if ops},
                   sorted(spans, key=lambda s: s[1]))


def load(path) -> Reduced:
    """Reduce an ``.xplane.pb`` file (gzipped when its name ends in
    ``.gz``)."""
    import jax

    path = str(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return reduce_profile(jax.profiler.ProfileData.from_serialized_xspace(f.read()))
    return reduce_profile(jax.profiler.ProfileData.from_file(path))


def union(intervals) -> List[Tuple[int, int]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _busy(red: Reduced, ops: List[Op]) -> List[Tuple[int, int]]:
    w0, w1 = red.window
    return union((max(op.start, w0), min(op.end, w1)) for op in ops)


def busy_s(red: Reduced) -> float:
    """Seconds in which an operation ran, averaged over the devices that
    ran any inside the window (0 when none did)."""
    if not red.devices:
        return 0.0
    total = sum(
        sum(e - s for s, e in _busy(red, ops)) for ops in red.devices.values()
    )
    return total * 1e-9 / len(red.devices)


def kernel_s(red: Reduced, pattern: str) -> float:
    """Summed seconds of the operations whose text matches ``pattern``,
    over every device, clipped to the window."""
    rx = re.compile(pattern)
    w0, w1 = red.window
    return 1e-9 * sum(
        min(op.end, w1) - max(op.start, w0)
        for ops in red.devices.values() for op in ops if rx.search(op.text)
    )


def kernel_calls(red: Reduced, pattern: str) -> int:
    """Operations whose text matches ``pattern``, over every device."""
    rx = re.compile(pattern)
    return sum(
        1 for ops in red.devices.values() for op in ops if rx.search(op.text)
    )


def top_ops(red: Reduced, n: int = 10) -> List[list]:
    """``[[name, seconds], ...]``: the ``n`` operation names that took the
    most device time in the window, summed over calls and devices."""
    w0, w1 = red.window
    by: Dict[str, int] = {}
    for ops in red.devices.values():
        for op in ops:
            by[op.name] = by.get(op.name, 0) + min(op.end, w1) - max(op.start, w0)
    return [[k, v * 1e-9] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def _labels(spans, times: List[int]) -> List[str]:
    """The innermost benchmark span (other than the window) open at each
    of ``times``: one sweep over span starts, span ends and the times."""
    events = []
    for i, (name, s, e) in enumerate(spans):
        if name != WINDOW and e > s:
            events.append((s, 1, i))
            events.append((e, 0, i))
    events += [(t, 2, j) for j, t in enumerate(times)]
    events.sort()
    open_, out = [], ["none"] * len(times)
    for _, kind, i in events:
        if kind == 1:
            open_.append(i)
        elif kind == 0:
            open_.remove(i)
        elif open_:
            out[i] = spans[open_[-1]][0]
    return out


def gaps(red: Reduced) -> List[Tuple[str, int, int]]:
    """``(label, start, end)`` of every idle stretch of every device in
    the window."""
    w0, w1 = red.window
    idle = []
    for ops in red.devices.values():
        t = w0
        for s, e in _busy(red, ops) + [(w1, w1)]:
            if s > t:
                idle.append((t, s))
            t = max(t, e)
    labels = _labels(red.spans, [(s + e) // 2 for s, e in idle])
    return [(lab, s, e) for lab, (s, e) in zip(labels, idle)]


def idle_gaps(red: Reduced, n: int = 10) -> List[list]:
    """``[[label, seconds], ...]``: idle device time by what the host was
    doing, the ``n`` largest labels first, averaged over devices."""
    by: Dict[str, int] = {}
    for label, s, e in gaps(red):
        by[label] = by.get(label, 0) + e - s
    k = max(len(red.devices), 1)
    return [[lab, v * 1e-9 / k]
            for lab, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]
