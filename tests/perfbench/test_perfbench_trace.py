"""The reduction from a profiler trace to device numbers: on hand-made
events, and on a trace recorded on a TPU v5e (``perfbench/testdata``),
against sums taken here straight from its events."""

from __future__ import annotations

import gzip
import re

import jax
import pytest
from conftest import DATA, RECORDED, REPO, recorded_ctx

from perfbench import harness
from perfbench import trace_reduce as tr

METRICS = REPO / "perfbench" / "metrics"


def _red(ops, spans):
    return tr.Reduced((0, 100), {"/device:TPU:0": [tr.Op(n, s, e, n) for n, s, e in ops]},
                      [("window", 0, 100)] + spans)


def test_union_merges_overlaps():
    assert tr.union([(5, 9), (0, 3), (2, 4), (9, 12)]) == [(0, 4), (5, 12)]


def test_busy_kernels_and_gaps_by_hand():
    red = _red([("a", 10, 30), ("b", 20, 40), ("k1", 60, 70), ("k2", 95, 120)],
               [("step", 0, 15), ("wait", 15, 100), ("put", 50, 55)])
    # busy: [10, 40) ∪ [60, 70) ∪ [95, 100) clipped to the window
    assert tr.busy_s(red) == pytest.approx(45e-9)
    assert tr.kernel_s(red, r"^k") == pytest.approx(15e-9)
    assert tr.kernel_calls(red, r"^k") == 2
    # idle: [0, 10) in step, [40, 60) mid 50 in put (innermost), [70, 95) in wait
    assert tr.gaps(red) == [("step", 0, 10), ("put", 40, 60), ("wait", 70, 95)]
    assert [lab for lab, _ in tr.idle_gaps(red)] == ["wait", "put", "step"]
    assert [s for _, s in tr.idle_gaps(red)] == pytest.approx([25e-9, 20e-9, 10e-9])
    assert [n for n, _ in tr.top_ops(red, 2)] == ["a", "b"]
    assert [s for _, s in tr.top_ops(red, 2)] == pytest.approx([20e-9, 20e-9])


def test_gap_outside_every_span_is_none():
    red = _red([("a", 50, 100)], [])
    assert tr.idle_gaps(red) == [["none", pytest.approx(50e-9)]]


def _reader(metric):
    return harness._module(METRICS / f"{metric}.py", f"reader_{metric}")


def _pattern(metric):
    return _reader(metric).PATTERN


def _events(path, red):
    """The device's operations inside the window, straight from the
    trace: (start, end, name)."""
    with gzip.open(path, "rb") as f:
        profile = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    w0, w1 = red.window
    (dev,) = [p for p in profile.planes if p.name == "/device:TPU:0"]
    return [(e.start_ns, e.end_ns, e.name) for line in dev.lines
            if line.name == tr.OPS_LINE for e in line.events
            if e.end_ns > w0 and e.start_ns < w1]


# Recorded on one v5e by a traced run of a cell; its file beside it says
# which kernel runs how many times a step, and under which spans.
@pytest.mark.parametrize("name, kernel, per_step, labels", [
    (name, kernel, per_step, set(rec["idle_labels"]))
    for name, rec in RECORDED.items()
    for kernel, per_step in rec.get("calls_per_step", {}).items()
])
def test_recorded_trace_reduces_as_its_events_say(name, kernel, per_step, labels):
    path = DATA / f"{name}.xplane.pb.gz"
    red = tr.load(path)
    events = _events(path, red)
    assert events and set(red.devices) == {"/device:TPU:0"}
    # busy: the events' intervals, clipped and merged by a plain sweep
    covered, last = 0, red.window[0]
    for s, e, _ in sorted(events):
        s, e = max(s, last), min(e, red.window[1])
        if e > s:
            covered += e - s
            last = e
    assert tr.busy_s(red) == pytest.approx(covered * 1e-9, rel=1e-9)
    # the kernel: one event per call, its durations summed
    steps = sum(1 for n, _, _ in red.spans if n == "wait")
    pattern = re.compile(_pattern(kernel))
    mine = [(s, e) for s, e, n in events if pattern.search(n)]
    assert len(mine) == tr.kernel_calls(red, pattern.pattern) == per_step * steps
    assert tr.kernel_s(red, pattern.pattern) == pytest.approx(
        sum(e - s for s, e in mine) * 1e-9, rel=1e-9)
    # every idle nanosecond is labelled by one of the benchmark's spans
    gaps = tr.idle_gaps(red, n=100)
    assert sum(s for _, s in gaps) == pytest.approx(
        red.window_s - tr.busy_s(red), rel=1e-9)
    assert {lab for lab, _ in gaps} <= labels | {"none"}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_trace_reads_as_recorded(name):
    """The readers of the metrics that count work from the model's shapes
    read, from a trace of one chip, the very numbers written beside it
    when it was recorded: counting work per chip changes nothing on
    one."""
    ctx = recorded_ctx(name)
    assert ctx.chips == 1
    reads = RECORDED[name]["reads"]
    assert reads
    for metric, value in reads.items():
        assert _reader(metric).read(ctx) == value, metric
