"""The readings of the program's own spans and kernel names: on
hand-made traces, on traces recorded on a TPU v5e with them
(``perfbench/testdata``), and through a traced run on the CPU."""

from __future__ import annotations

import gzip
import json
import types

import jax
import pytest
from conftest import REPO, run_cell

from perfbench import harness
from perfbench import program_spans as ps
from perfbench import trace_reduce as tr
from perfbench.models import gcn

DATA = REPO / "perfbench" / "testdata"
METRICS = REPO / "perfbench" / "metrics"
V5E = harness.peaks_for("TPU v5 lite")
HOST = ("session.put_ms", "session.handle_ms", "engine.lookup_ms", "engine.call_ms")
GCN = json.loads((REPO / "perfbench" / "configs" / "gcn-arxiv.json").read_text())


def _reader(metric):
    return harness._module(METRICS / f"{metric}.py", f"reader_{metric}").read


def _red(ops=(), bench=(), program=None, window=(0, 1000)):
    red = tr.Reduced(window, {"/device:TPU:0": [tr.Op(n, s, e, n) for n, s, e in ops]},
                     [("window",) + window] + list(bench))
    if program is not None:
        red.program = sorted(program, key=lambda s: (s[1], -s[2]))
    return red


def _ctx(red, steps, cfg=None):
    return types.SimpleNamespace(trace=red, steps=steps, peaks=V5E,
                                 cell=types.SimpleNamespace(cfg=cfg or {}))


# two steps: lookup (lowering inside) and call (dispatch inside) under
# the step span; a span of another thread inside the first step's time
STEPS = [
    ("session.step", 100, 400, 0), ("engine.lookup", 120, 200, 0),
    ("engine.lower", 130, 180, 0), ("engine.call", 250, 380, 0),
    ("engine.dispatch", 300, 370, 0), ("engine.call", 150, 390, 1),
    ("session.step", 500, 700, 0), ("engine.call", 550, 650, 0),
    ("session.put", 900, 1100, 0),  # ends after the window: not counted
]


def test_self_time_leaves_out_the_spans_nested_in_it():
    red = _red(program=STEPS)
    # (300 - 80 - 130) + (200 - 100): the other thread's span is not nested
    assert ps.self_ns(red, "session.step") == 190
    assert ps.total_ns(red, "engine.call") == 130 + 240 + 100
    assert ps.self_ns(red, "engine.call") == (130 - 70) + 240 + 100
    assert ps.self_ns(red, "engine.lookup") == 80 - 50


def test_per_step_milliseconds():
    ctx = _ctx(_red(program=STEPS), steps=2)
    assert ps.span_ms(ctx, "engine.lookup") == pytest.approx(80e-6 / 2)
    assert ps.self_ms(ctx, "session.step") == pytest.approx(190e-6 / 2)
    assert _reader("engine.call_ms")(ctx) == pytest.approx(470e-6 / 2)
    assert _reader("session.handle_ms.minibatch")(ctx) == pytest.approx(190e-6 / 2)


@pytest.mark.parametrize("metric", [m + s for m in HOST for s in ("", ".minibatch")])
def test_host_metrics_read_nothing_without_program_spans(metric):
    read = _reader(metric)
    assert read(_ctx(None, steps=2)) is None
    assert read(_ctx(_red(), steps=2)) is None            # reduced without them
    assert read(_ctx(_red(program=[]), steps=2)) is None  # a program without them
    # only a span that ends after the window
    assert read(_ctx(_red(program=[STEPS[-1]]), steps=2)) is None


def test_idle_gaps_carry_the_innermost_program_span():
    red = _red(ops=[("a", 0, 100), ("b", 300, 400), ("c", 600, 700), ("d", 800, 1000)],
               bench=[("put", 90, 350), ("step", 450, 790)],
               program=[("session.put", 95, 340, 0), ("session.stats", 150, 300, 0),
                        ("session.step", 460, 780, 0), ("engine.call", 460, 560, 0)])
    # gaps [100, 300) mid 200, [400, 600) mid 500, [700, 800) mid 750
    assert tr.gaps(red) == [("put/session.stats", 100, 300),
                            ("step/engine.call", 400, 600),
                            ("step/session.step", 700, 800)]
    assert ps._bench_gaps(red) == [("put", 100, 300), ("step", 400, 600),
                                   ("step", 700, 800)]
    # outside every program span the label is the benchmark's alone
    red.program = [("session.step", 460, 480, 0)]
    assert tr.gaps(red) == ps._bench_gaps(red)
    gaps = tr.idle_gaps(red, n=100)
    assert sum(s for _, s in gaps) == pytest.approx(red.window_s - tr.busy_s(red))


def _launches(n):
    """``n`` gather launches of 10 ns each."""
    return [(f"%gather_join.{i} = f32[65536,1,128] custom-call(), "
             "custom_call_target=\"tpu_custom_call\"", 10 * i, 10 * i + 10)
            for i in range(n)]


@pytest.mark.parametrize("launches, reads", [(6, True), (12, True), (5, False),
                                             (0, False), (7, False)])
def test_gather_roofline_needs_whole_launches_per_gather(launches, reads):
    read = _reader("gather_join_roofline")
    ops = _launches(launches) + [("%segment_sum.3 = custom-call tpu_custom_call", 900, 950)]
    got = read(_ctx(_red(ops=ops), steps=2, cfg=GCN))
    if not reads:
        assert got is None
        return
    e = GCN["edges"] + GCN["nodes"]
    least = sum(4 * (2 * e * d + e) for d in (128, 256, 256)) / V5E["hbm_bytes_per_s"]
    assert got == pytest.approx(100 * 2 * least / (launches * 10e-9))


@pytest.mark.parametrize("name", ["gcn-arxiv.full", "logreg-epsilon.full"])
def test_trace_without_program_spans_reduces_as_before(name):
    with gzip.open(DATA / f"{name}.xplane.pb.gz", "rb") as f:
        profile = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    red, before = tr.reduce_profile(profile), ps._bench_reduce_profile(profile)
    assert ps.program(red) == []
    assert (red.window, red.devices, red.spans) == (before.window, before.devices,
                                                    before.spans)
    assert tr.gaps(red) == ps._bench_gaps(before)


def test_recorded_minibatch_trace_splits_the_session_host_time():
    """A few mini-batch steps on one v5e with the program's spans: the
    four host metrics read, and together they are the benchmark's own
    ``put`` and ``step`` spans to within 5 %."""
    red = tr.load(DATA / "logreg-epsilon.minibatch.xplane.pb.gz")
    steps = sum(1 for n, _, _ in red.spans if n == "wait")
    ctx = _ctx(red, steps)
    parts = {m: _reader(m + ".minibatch")(ctx) for m in HOST}
    assert all(v is not None and v > 0 for v in parts.values()), parts
    host = _reader("session.host_ms.minibatch")(ctx)
    assert sum(parts.values()) == pytest.approx(host, rel=0.05)
    names = {n for n, _, _, _ in ps.program(red)}
    assert {"engine.lower", "engine.plan"}.isdisjoint(names)
    # every idle nanosecond labelled, program spans among the labels
    gaps = tr.idle_gaps(red, n=1000)
    assert sum(s for _, s in gaps) == pytest.approx(red.window_s - tr.busy_s(red),
                                                    rel=1e-9)
    assert any("/" in lab for lab, _ in gaps)


def test_recorded_gcn_trace_reads_the_gather_roofline():
    """A GCN window on one v5e with the gather's launches named: the
    gather's roofline reads, the other kernels read as before, and no
    program span runs in the window (the user's jitted step is cached)."""
    red = tr.load(DATA / "gcn-arxiv.full.gather_join.xplane.pb.gz")
    steps = sum(1 for n, _, _ in red.spans if n == "wait")
    ctx = _ctx(red, steps, cfg=GCN)
    assert tr.kernel_calls(red, r"%closed_call") == 0
    share = _reader("gather_join_roofline")(ctx)
    assert share is not None and 0 < share < 100
    ctx.work = gcn.work(GCN, None)
    for metric in ("segment_sum_roofline", "blocked_matmul_roofline"):
        assert 0 < _reader(metric)(ctx) < 100
    w0, w1 = red.window
    assert not [sp for sp in ps.program(red) if w0 <= sp[1] and sp[2] <= w1]


@pytest.mark.parametrize("cell, suffix", [("logreg-epsilon.full", ""),
                                          ("logreg-epsilon.minibatch", ".minibatch")])
def test_traced_run_reports_the_host_metrics(tiny_root, cell, suffix):
    rc, res, err = run_cell(tiny_root, cell, trace=True)
    assert rc == 0 and res["correct"], err
    for m in HOST:
        assert res["metrics"][m + suffix]["value"] > 0
    assert "gather_join_roofline" not in res["metrics"]
