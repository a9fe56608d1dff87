"""Work counted from the model's shapes, against hand sums at small
shapes, and the readers that turn it into roofline and peak shares."""

from __future__ import annotations

import pytest
from conftest import REPO

from perfbench import harness, metrics_common, trace_reduce
from perfbench.feed import Feed
from perfbench.models import gcn, logreg

V5E = harness.peaks_for("TPU v5 lite")


def test_gcn_work_by_hand():
    cfg = {"nodes": 10, "edges": 30, "features": 4, "hidden": 8, "classes": 3}
    w = gcn.work(cfg, Feed({"batch_rows": None}, 10, 0))
    e = 30 + 10  # a self loop per node
    assert w["kernels"]["segment_sum"] == [
        (40 * 4, 4 * (40 * 4 + 40 + 10 * 4)),
        (40 * 8, 4 * (40 * 8 + 40 + 10 * 8)),
        (40 * 8, 4 * (40 * 8 + 40 + 10 * 8)),
    ]
    assert w["kernels"]["gather_join"] == [(0, 4 * (2 * 40 * d + 40)) for d in (4, 8, 8)]
    # the kernel takes the two forward products; all five count as work
    mm = w["kernels"]["blocked_matmul"]
    assert mm == [(2 * 10 * 4 * 8, 4 * (10 * 4 + 4 * 8 + 10 * 8)),
                  (2 * 10 * 8 * 3, 4 * (10 * 8 + 8 * 3 + 10 * 3))]
    assert w["flops"] == 2 * e * (4 + 8 + 8) + 640 + 480 + 480 + 480 + 640


@pytest.mark.parametrize("batch, rows", [(None, 100), (25, 25)])
def test_logreg_work_by_hand(batch, rows):
    cfg = {"rows": 100, "features": 7}
    w = logreg.work(cfg, Feed({"batch_rows": batch}, 100, 0))
    one = (2 * rows * 7, 4 * (rows * 7 + 7 + rows))
    assert w["kernels"]["blocked_matmul"] == [one, one]
    assert w["flops"] == 4 * rows * 7


def test_feed_draws_whole_batches_of_one_permutation():
    feed = Feed({"batch_rows": 3}, 10, seed=2**31 + 5)
    assert feed.batches.shape == (3, 3)  # three whole batches of ten rows
    assert len(set(feed.batches.ravel())) == 9  # no row twice
    again = Feed({"batch_rows": 3}, 10, seed=2**31 + 5)
    assert (again.batches == feed.batches).all()
    full = Feed({"batch_rows": None}, 10, seed=1)
    assert full.full and full.batches is None and full.batch_rows == 10


def test_roofline_takes_the_binding_bound():
    # 197e9 operations take 1 ms at the bf16 peak; 819e6 bytes take 1 ms
    calls = [(197e9, 1.0), (1.0, 2 * 819e6)]
    assert metrics_common.roofline_s(calls, V5E) == pytest.approx(1e-3 + 2e-3)


def _ctx(ops, steps=2, work=None, chips=1):
    """A context whose trace holds ``ops`` on each of ``chips`` chips."""
    red = trace_reduce.Reduced((0, 10_000_000),
                               {f"/device:TPU:{i}": list(ops) for i in range(chips)},
                               [("window", 0, 10_000_000)])
    return harness.Context(cell=None, chips=chips, work=work, steps=steps,
                           window_s=0.01, setup_s=1.0, memory_peak_bytes=2**30,
                           compiles=0, trace=red, peaks=V5E)


def _reader(name):
    return harness._module(REPO / "perfbench" / "metrics" / f"{name}.py",
                           f"reader_{name}")


SIGMA = '%segment_sum.3 = f32[8,8] custom-call(), custom_call_target="tpu_custom_call"'


def test_kernel_roofline_reader():
    # two steps, each one Σ call that the roofline puts at 1 ms of bytes
    work = {"flops": 0, "kernels": {"segment_sum": [(0, 819e6)]}}
    ops = [trace_reduce.Op("k", 0, 2_000_000, SIGMA),
           trace_reduce.Op("k", 2_000_000, 4_000_000, SIGMA),
           trace_reduce.Op("f", 4_000_000, 5_000_000, "fusion")]
    ctx = _ctx(ops, steps=2, work=work)
    assert _reader("segment_sum_roofline").read(ctx) == pytest.approx(50.0)
    # no event of the kernel: nothing to read, never 0
    assert _reader("blocked_matmul_roofline").read(ctx) is None
    # calls that are not the counted ones: nothing to read
    assert _reader("segment_sum_roofline").read(_ctx(ops[1:], 2, work)) is None


def test_mfu_and_idle_readers():
    work = {"flops": 197e9, "kernels": {}}  # 1 ms of the peak per step
    ops = [trace_reduce.Op("a", 1_000_000, 3_000_000, "a"),
           trace_reduce.Op("b", 2_000_000, 6_000_000, "b")]
    ctx = _ctx(ops, steps=5, work=work)
    assert _reader("step.mfu").read(ctx) == pytest.approx(50.0)
    assert _reader("device.idle_share").read(ctx) == pytest.approx(50.0)
    assert _reader("peak_hbm_gib").read(ctx) == 1.0


GATHER = '%gather_join.7 = f32[65536,1,8] custom-call(), custom_call_target="tpu_custom_call"'


def test_shares_hold_on_four_chips():
    """One chip's trace copied to four planes: each chip makes the calls
    the model counts for it, so the kernels' shares read as on one chip,
    and the whole step's share of four chips' peak is a quarter."""
    work = {"flops": 197e9, "kernels": {"segment_sum": [(0, 819e6)],
                                        "gather_join": [(0, 819e6)] * 2}}
    ops = [trace_reduce.Op("k", 0, 2_000_000, SIGMA),
           trace_reduce.Op("k", 2_000_000, 4_000_000, SIGMA),
           trace_reduce.Op("g", 4_000_000, 6_000_000, GATHER),
           trace_reduce.Op("g", 6_000_000, 7_000_000, GATHER),
           trace_reduce.Op("g", 7_000_000, 9_000_000, GATHER),
           trace_reduce.Op("g", 9_000_000, 10_000_000, GATHER)]
    one, four = _ctx(ops, work=work), _ctx(ops, work=work, chips=4)
    assert four.trace.window_s == one.trace.window_s
    for metric in ("segment_sum_roofline", "gather_join_roofline"):
        assert _reader(metric).read(one) is not None
        assert _reader(metric).read(four) == pytest.approx(_reader(metric).read(one))
    assert _reader("segment_sum_roofline").read(one) == pytest.approx(50.0)
    assert _reader("gather_join_roofline").read(one) == pytest.approx(200 * 2 / 6)
    assert _reader("step.mfu").read(four) == pytest.approx(_reader("step.mfu").read(one) / 4)
    # one chip's events alone under a four-chip cell: not the counted calls
    alone = _ctx(ops, work=work)
    alone.chips = 4
    assert _reader("segment_sum_roofline").read(alone) is None
    assert _reader("gather_join_roofline").read(alone) is None
