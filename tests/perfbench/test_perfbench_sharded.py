"""The readers of a cell on four chips (``join_agg_roofline``,
``collective.exposed_ms``) on made-up four-device traces, and the work
that ``gcn-products.nnz4`` counts."""

from __future__ import annotations

import json

import pytest
from conftest import REPO

from perfbench import harness, trace_reduce

V5E = harness.peaks_for("TPU v5 lite")

LOOP = "%while.7 = (s32[], f32[8,8]) while(%tuple.3), condition=%c, body=%b"
GATHER = '%gather_join.2 = f32[65536,1,8] custom-call(), custom_call_target="tpu_custom_call"'
SIGMA = '%segment_sum.4 = f32[8,8] custom-call(), custom_call_target="tpu_custom_call"'
FUSION = "%fusion.3 = f32[8,8] fusion(%p), kind=kLoop"
RS = "%reduce-scatter.1 = f32[2,8] reduce-scatter(%p), dimensions={0}"
AG = "%all-gather-start.2 = (f32[2,8], f32[8,8]) all-gather-start(%p), dimensions={0}"
#: a TPU's reduce-scatter: a fusion that calls the collective
RS_FUSION = "%fusion.61 = f32[2,8] fusion(f32[8,8] %p), kind=kCustom, calls=%all-reduce-scatter"
#: a fusion whose operand is a collective's output is no collective
AFTER_AG = "%fusion.9 = f32[8,8] fusion(f32[8,8] %all-gather.1), kind=kLoop, calls=%fused_computation"


def _reader(name):
    return harness._module(REPO / "perfbench" / "metrics" / f"{name}.py",
                           f"reader_{name}")


def _ctx(ops, steps, work, chips=4):
    """A context whose trace holds ``ops`` on each of ``chips`` chips."""
    red = trace_reduce.Reduced((0, 100_000), {f"/device:TPU:{i}": list(ops)
                                              for i in range(chips)},
                               [("window", 0, 100_000)])
    return harness.Context(cell=None, chips=chips, work=work, steps=steps,
                           window_s=1e-4, setup_s=1.0, memory_peak_bytes=None,
                           compiles=0, trace=red, peaks=V5E)


def _call(start, length):
    """One fused call: a loop holding a gather's launch loop and two Σ
    launches, each block's ops inside the loop's span."""
    end = start + length
    return [trace_reduce.Op("w", start, end, LOOP),
            trace_reduce.Op("w", start + 1, start + 3, LOOP),
            trace_reduce.Op("g", start + 1, start + 3, GATHER),
            trace_reduce.Op("s", start + 4, start + 6, SIGMA),
            trace_reduce.Op("s", start + 6, end - 1, SIGMA)]


def test_join_agg_roofline_reads_its_loops():
    # one step of three calls a chip, each put at 10 µs by its bytes
    work = {"flops": 0, "kernels": {"join_agg": [(0, 819e9 * 1e-5)] * 3}}
    ops = _call(0, 20_000) + _call(30_000, 20_000) + _call(60_000, 20_000)
    ops.append(trace_reduce.Op("w", 85_000, 90_000, LOOP))  # a loop of no call
    ops.append(trace_reduce.Op("g", 86_000, 87_000, GATHER))
    read = _reader("join_agg_roofline").read
    # 30 µs of least time a chip over 60 µs of loops a chip
    assert read(_ctx(ops, 1, work)) == pytest.approx(50.0)
    # a call missing on one chip: not the counted calls, nothing to read
    red = _ctx(ops, 1, work).trace
    red.devices["/device:TPU:3"] = ops[5:]
    ctx = _ctx(ops, 1, work)
    ctx.trace = red
    assert read(ctx) is None
    assert read(_ctx(ops, 2, work)) is None
    assert read(_ctx([op for op in ops if op.text != SIGMA], 1, work)) is None


def test_collective_exposed_ms_counts_what_nothing_overlaps():
    read = _reader("collective.exposed_ms").read
    work = {"flops": 0, "kernels": {}}
    hidden = [trace_reduce.Op("f", 0, 50_000, FUSION),
              trace_reduce.Op("c", 10_000, 30_000, RS)]
    assert read(_ctx(hidden, 1, work)) == 0.0
    # 20 µs exposed, half of it under another op: 10 µs over two steps
    exposed = [trace_reduce.Op("f", 0, 20_000, FUSION),
               trace_reduce.Op("c", 10_000, 30_000, RS),
               trace_reduce.Op("a", 40_000, 50_000, AG),
               trace_reduce.Op("f", 40_000, 50_000, FUSION)]
    assert read(_ctx(exposed, 2, work)) == pytest.approx(0.005)
    # the mean over chips: two chips of four expose 10 µs each
    ctx = _ctx(exposed, 1, work)
    ctx.trace.devices["/device:TPU:2"] = hidden
    ctx.trace.devices["/device:TPU:3"] = hidden
    assert read(ctx) == pytest.approx(0.005)
    assert read(_ctx([trace_reduce.Op("f", 0, 9, FUSION)], 1, work)) is None
    assert read(_ctx([trace_reduce.Op("f", 0, 9, AFTER_AG)], 1, work)) is None
    # the TPU's fused reduce-scatter counts: 30 µs, 10 µs of it under a
    # fusion that merely reads a collective's output
    fused = [trace_reduce.Op("c", 0, 30_000, RS_FUSION),
             trace_reduce.Op("f", 20_000, 40_000, AFTER_AG)]
    assert read(_ctx(fused, 1, work)) == pytest.approx(0.02)


def test_products_work_by_hand():
    """The products step's work over all chips, and each chip's fused
    join-aggregate per convolution: 2·E_c·D operations, E_c·12 + E_c·D·4
    + S·D·4 bytes, E_c = 64,308,169 edges padded to four chips' worth,
    over four. The gathers and Σs inside are the one-chip model's at
    E_c, in parts of one launch each: at D = 100, 5 blocks of 3,473,408
    edges (53 gather launches of 65,536 rows, and 2 Σ launches of
    6,784 + 19,134 visits); at D = 256, 13 blocks of 1,310,720 edges (20
    gather launches, 2 Σ launches of 2,560 + 19,134 visits)."""
    from perfbench.feed import Feed
    from perfbench.models import gcn, gcn_sharded

    cfg = json.loads((REPO / "perfbench" / "configs" / "gcn-products.json").read_text())
    feed = Feed({"batch_rows": None}, cfg["nodes"], 0)
    w = gcn_sharded.work(cfg, feed)
    ec, n = 16_077_043, 2_449_029
    dims = (100, 256, 256)
    assert w["kernels"]["join_agg"] == [
        (2 * ec * d, 12 * ec + 4 * ec * d + 4 * n * d) for d in dims]
    gathers, sums = w["kernels"]["gather_join"], w["kernels"]["segment_sum"]
    assert len(gathers) == 5 * 53 + 2 * 13 * 20 == 785
    assert len(sums) == 5 * 2 + 2 * 13 * 2 == 62
    one_chip = gcn.work(dict(cfg, edges=ec - n), feed)["kernels"]
    for got, parts, want in ((gathers, (265, 260, 260), one_chip["gather_join"]),
                             (sums, (10, 26, 26), one_chip["segment_sum"])):
        at = 0
        for k, (flops, nbytes) in zip(parts, want):
            assert sum(f for f, _ in got[at:at + k]) == pytest.approx(flops)
            assert sum(b for _, b in got[at:at + k]) == pytest.approx(nbytes)
            at += k
    assert w["flops"] == gcn.work(cfg, feed)["flops"]
    # the convolutions' products and adds; two forward dense products,
    # and backward dW2, the hidden layer's input gradient and dW1
    assert w["flops"] == (2 * 64_308_169 * (100 + 256 + 256)
                          + 2 * 2 * n * 100 * 256 + 3 * 2 * n * 256 * 47)
