"""COO nnz-sharding scale lane: the same GCN grad step with the edge
relation replicated vs nnz-sharded, on the host mesh.

The paper's scaling claim needs the *edge list* — the largest array in a
graph program — distributed. This lane measures exactly that on the
8-virtual-device CI mesh:

  replicated — a 1×N (model-only) host mesh: the planner has no data
               axes, the CooRelation is replicated on every device (the
               pre-COO-sharding behaviour)
  sharded    — an N×1 (data-only) host mesh: the planner places the nnz
               rows on the data axis (``data:shard_nnz_left``) and the
               Σ-by-dst runs as per-shard segment-sum + scatter collective

Per row we record the jitted step time and, in ``derived``, the measured
**per-device peak bytes of the edge relation** (max over devices of the
keys+values shard bytes actually placed by the compiled in_shardings) —
the sharded lane must show the ~N× reduction. Results are asserted to
agree to atol 1e-5 across lanes.

A third ``oocore`` lane extends the scaling axis past what fits at
all: the same step through ``Database(memory_budget=...)`` with the
budget set so E is 4× past the simulated device memory — the edge
relation spills to the host chunk store and streams back through
owner-partitioned chunk waves on the sharded mesh, matching the in-core
lanes to atol 1e-5. ``tools/check_bench.py --suites coo_scale`` gates
all three lanes against the committed baseline.

Runs meaningfully under the tier1-spmd lane's
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; on a single
device both mesh lanes degenerate to the same placement and the rows
say so.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.core import fra
from repro.core.autodiff import ra_autodiff
from repro.core.engine import StreamedCompiled, engine_for
from repro.core.relation import DenseRelation
from repro.launch.mesh import make_host_mesh
from repro.relational.gcn import gcn_square_loss, partitioned_edges

from .common import record, timeit

ATOL = 1e-5

GRAPHS = [
    ("arxiv-mini", 2_000, 160_000, 32),
    ("pubmed-mini", 500, 20_000, 16),
]


def _env(rng, n: int, e: int, d: int, num_shards: int):
    src = rng.integers(0, n, size=e)
    dst = rng.integers(0, n, size=e)
    w = rng.normal(size=e) / np.sqrt(e / n)
    edge = partitioned_edges(
        np.stack([src, dst], 1), w.astype(np.float32), n, num_shards
    )
    return {
        "Edge": edge,
        "Node": DenseRelation(
            jnp.asarray(rng.normal(size=(n, d)), jnp.float32), 1
        ),
    }


def _edge_bytes_per_device(comp, env) -> int:
    """Max over devices of the edge relation's placed shard bytes (keys +
    values), read off the compiled step's actual in_shardings."""
    sh_don, sh_kept = comp.in_shardings
    target = {**sh_kept, **sh_don}["Edge"]
    placed = jax.device_put(comp._padded(env)["Edge"], target)
    per_device: dict = {}
    for arr in (placed.keys, placed.values):
        for s in arr.addressable_shards:
            per_device[s.device.id] = per_device.get(s.device.id, 0) + int(
                np.prod(s.data.shape) * s.data.dtype.itemsize
            )
    return max(per_device.values())


def run() -> None:
    n_dev = jax.device_count()
    rng = np.random.default_rng(7)
    for name, n, e, d in GRAPHS:
        if n_dev < 2:
            record(
                f"coo_scale/{name}/replicated", 0.0,
                f"skipped=single_device;devices={n_dev}",
            )
            continue
        env = _env(rng, n, e, d, n_dev)
        prog = ra_autodiff(gcn_square_loss(n))
        eng = engine_for(prog)
        low = eng.lower(env)

        lanes = {
            # model-only mesh: no data axes -> the COO is replicated
            "replicated": make_host_mesh(model=n_dev),
            # data-only mesh: nnz rows sharded n_dev ways
            "sharded": make_host_mesh(model=1),
        }
        base = None
        for lane, mesh in lanes.items():
            comp = low.compile(mesh=mesh)
            out, grads = comp(env)
            leaves = [np.asarray(out.data)] + [
                np.asarray(
                    g.values if hasattr(g, "values") else g.data
                )
                for _, g in sorted(grads.items())
            ]
            if base is None:
                base = leaves
            else:
                for got, want in zip(leaves, base):
                    np.testing.assert_allclose(got, want, atol=ATOL, rtol=1e-5)
            ebytes = _edge_bytes_per_device(comp, env)
            placement = comp.placements["Edge"]
            us = timeit(lambda: comp(env), iters=5, warmup=2)
            record(
                f"coo_scale/{name}/{lane}", us,
                f"edge_bytes_per_device={ebytes};nnz_data_dim="
                f"{placement['data']};E={e};n={n};d={d}",
            )

        # oocore lane: E extended past the simulated device budget — the
        # edge relation is 4x the headroom the budget leaves after the
        # node features, so the same step must stream chunk waves
        from repro.core.planner import _rel_bytes

        edge_bytes = _rel_bytes(env["Edge"])
        node_bytes = _rel_bytes(env["Node"])
        budget = node_bytes + edge_bytes / 4
        db = repro.Database(
            mesh=lanes["sharded"], memory_budget=budget
        )
        db.put("Edge", env["Edge"])
        db.put("Node", env["Node"].data, keys=("node",))
        q = fra.Query(prog.forward.root, inputs=("Edge", "Node"))
        h = db.query(q)
        out, grads = h.step(wrt=("Edge", "Node"))
        assert isinstance(h.last, StreamedCompiled), "budget did not stream"
        leaves = [np.asarray(out.data)] + [
            np.asarray(g.values if hasattr(g, "values") else g.data)
            for _, g in sorted(grads.items())
        ]
        for got, want in zip(leaves, base):
            np.testing.assert_allclose(got, want, atol=ATOL, rtol=1e-5)
        us = timeit(lambda: h.step(wrt=("Edge", "Node")), iters=5, warmup=2)
        record(
            f"coo_scale/{name}/oocore", us,
            f"waves={h.last.num_waves};budget={budget:.0f};"
            f"edge_bytes={edge_bytes};E={e};n={n};d={d}",
        )


if __name__ == "__main__":
    from .common import ROWS, emit_header, emit_json

    emit_header()
    run()
    emit_json("BENCH_coo_scale.json", ROWS)
