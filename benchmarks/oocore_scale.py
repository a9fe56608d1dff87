"""Out-of-core streaming lane: the GCN grad step with the edge relation
oversubscribed ≥4× past a simulated device-memory budget.

Two lanes per graph, both through the ``Database`` front door so the
streamed path is the one users actually hit:

  incore — ``Database()`` with no budget: one jitted step over the whole
           graph (the oracle, and the pre-PR behaviour)
  oocore — ``Database(memory_budget=...)`` with the budget set to
           node-bytes + edge-bytes/4: the planner streams the
           owner-partitioned edge relation through ≥4 double-buffered
           chunk waves, Σ accumulating across waves

Results are asserted to agree to atol 1e-5 before anything is recorded,
so a silently-wrong streamed step can never post a timing. ``derived``
carries the wave count, the oversubscription ratio (edge bytes over the
budget headroom left after resident relations), and the spill counters.

Runs on any device count — streaming is a host↔device tier decision,
not a mesh one. The tier1-oocore CI lane runs it on the 4×2 host mesh
and gates the emitted BENCH_oocore_scale.json against the committed
baseline via ``tools/check_bench.py --suites oocore_scale``.
"""

from __future__ import annotations

import numpy as np

import repro
from repro.core.engine import StreamedCompiled
from repro.core.planner import _rel_bytes
from repro.relational.gcn import gcn_square_loss

from .common import record, timeit

ATOL = 1e-5

#: name, nodes, edges, feature dim — sized so the budgeted lane streams
#: ≥4 waves while staying inside the CI time box
GRAPHS = [
    ("pubmed-mini", 500, 20_000, 16),
    ("arxiv-mini", 1_000, 80_000, 32),
]


def _fill(db, rng, n: int, e: int, d: int):
    import jax.numpy as jnp

    from repro.relational.gcn import partitioned_edges

    edge = partitioned_edges(
        np.stack([rng.integers(0, n, e), rng.integers(0, n, e)], 1),
        (rng.normal(size=e) / np.sqrt(e / n)).astype(np.float32),
        n,
        8,
    )
    db.put("Edge", edge)
    db.put(
        "Node",
        jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
        keys=("node",),
    )
    return db


def _leaves(loss, grads):
    out = [np.asarray(loss.data)]
    for _, g in sorted(grads.items()):
        out.append(np.asarray(g.values if hasattr(g, "values") else g.data))
    return out


def run() -> None:
    for seed, (name, n, e, d) in enumerate(GRAPHS, start=17):
        q = gcn_square_loss(n)
        wrt = ("Edge", "Node")

        db0 = _fill(repro.Database(), np.random.default_rng(seed), n, e, d)
        h0 = db0.query(q)
        l0, g0 = h0.step(wrt=wrt)
        base = _leaves(l0, g0)
        us = timeit(lambda: h0.step(wrt=wrt), iters=5, warmup=2)
        edge_bytes = _rel_bytes(db0.get("Edge"))
        node_bytes = _rel_bytes(db0.get("Node"))
        record(
            f"oocore_scale/{name}/incore", us,
            f"edge_bytes={edge_bytes};E={e};n={n};d={d}",
        )

        # edge relation ≥4× the headroom the budget leaves after the
        # resident (node) relation -> the planner must stream ≥4 waves
        budget = node_bytes + edge_bytes / 4
        headroom = budget - node_bytes
        assert edge_bytes >= 4 * headroom
        db = _fill(
            repro.Database(memory_budget=budget),
            np.random.default_rng(seed), n, e, d,
        )
        h = db.query(q)
        l1, g1 = h.step(wrt=wrt)
        assert isinstance(h.last, StreamedCompiled), "budget did not stream"
        waves = h.last.num_waves
        assert waves >= 4, f"expected >=4 waves, planned {waves}"
        for got, want in zip(_leaves(l1, g1), base):
            np.testing.assert_allclose(got, want, atol=ATOL, rtol=1e-5)
        us = timeit(lambda: h.step(wrt=wrt), iters=5, warmup=2)
        st = db.counters()["spill"]
        record(
            f"oocore_scale/{name}/oocore", us,
            f"waves={waves};oversub={edge_bytes / headroom:.1f}"
            f";spilled_bytes={st['spilled_bytes']}"
            f";fetched_chunks={st['fetched_chunks']}",
        )


if __name__ == "__main__":
    from .common import ROWS, emit_header, emit_json

    emit_header()
    run()
    emit_json("BENCH_oocore_scale.json", ROWS)
