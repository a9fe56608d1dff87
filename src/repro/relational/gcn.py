"""Graph convolution as a relational join-aggregate (paper §1, §6).

  h'_dst = Σ_{(src,dst,w) ∈ Edge} w · h_src

Forward: Edge ⋈ Node (gather) + Σ-by-dst (segment sum), lowered as one
fused join-aggregate that runs in blocks of edges when their messages
would not fit one (``compiler._coo_join_agg``). Backward — both ∂/∂h (the
reversed-edge convolution) and ∂/∂w (per-edge h·g dot, E values) — is
the RA-autodiff-generated query, compiled the same way. The Pallas
gather and segsum kernels are the TPU hot path (kernels/gather,
kernels/segsum).

Forward and backward step through the ambient ``Database`` session
(``core.session.current()``): the program is built once, lowered per
(graph-size, feature-dim) signature, and reused as a jitted ``Compiled``
across training steps. Under an activated mesh-bearing session
(``with repro.Database(mesh=...).activate():``) the 2-D planner places
the relations on the session's (data × model) mesh, including the edge
CooRelation's nnz row dimension over the data axes
(``data:shard_nnz_*`` plans): the gather join and Σ-by-dst then run
per shard inside ``shard_map``, in blocks of edges, and reduce-scatter
their partial sums to the row owners, so neither the largest array in
the program — the edge list — nor the edges' messages ever has to fit
one device, and the convolution's output comes back owner-sharded by
rows. This holds inside a user's own ``jax.jit`` too.
``partitioned_edges`` pre-sorts edges by dst (owner partition), which
the planner prices at its edge-cut estimate — or, when the session's
catalog tracks the edge relation's statistics, at the measured
distinct-dst fraction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fra, session
from repro.core.autodiff import ra_autodiff
from repro.core.kernels import ADD, MUL, SQUARE, SUM_CHUNK, scale_kernel
from repro.core.keys import EMPTY_KEY, TRUE, L, eq_pred, identity_key, jproj
from repro.core.relation import CooRelation, DenseRelation, owner_partition


def partitioned_edges(
    edge_keys, edge_w, n_nodes: int, num_shards: int
) -> CooRelation:
    """Edge relation in the owner-partitioned nnz layout: rows sorted by
    dst (key column 1 — the Σ-by-dst segment key) and padded to a
    ``num_shards`` multiple, so an nnz sharding gives each shard a
    contiguous destination range and the planner prices the scatter at
    ``planner.EDGE_CUT_LOCAL``. Returns the CooRelation to train with —
    its row order is the order edge-weight gradients come back in."""
    rel = CooRelation(
        jnp.asarray(edge_keys, jnp.int32),
        jnp.asarray(edge_w),
        (n_nodes, n_nodes),
    )
    return owner_partition(rel, num_shards, dim=1)


def _conv(edge):
    """Σ_dst(Edge ⋈ Node): each edge's w · h_src, summed by dst."""
    join = fra.Join(
        eq_pred((0, 0)),        # edge.src == node.id
        jproj(L(1)),            # output keyed by dst
        MUL,                    # w · h_src (scalar × chunk)
        edge,
        fra.scan("Node", 1),
    )
    return fra.Agg(identity_key(1), ADD, join)


def gcn_square_loss(n_nodes=None, *, const_edges: bool = False) -> fra.Query:
    """One graph convolution and a sum-of-squares loss as one query,
    Σ_v conv(v)² — divided by ``n_nodes`` when it is given. Edge weights
    are differentiable inputs, or constants with ``const_edges`` (Node
    is then the only input)."""
    edge = (fra.const if const_edges else fra.scan)("Edge", 2)
    sq = fra.Select(TRUE, identity_key(1), SQUARE, _conv(edge))
    loss = fra.Agg(
        EMPTY_KEY, ADD, fra.Select(TRUE, identity_key(1), SUM_CHUNK, sq)
    )
    if n_nodes is not None:
        loss = fra.Select(TRUE, identity_key(0), scale_kernel(1.0 / n_nodes), loss)
    return fra.Query(loss, inputs=("Node",) if const_edges else ("Edge", "Node"))


@functools.cache
def _gcn_prog():
    # differentiable edge weights
    q = fra.Query(_conv(fra.scan("Edge", 2)), inputs=("Edge", "Node"))
    prog = ra_autodiff(q)
    scans = {s.name: s.id for s in q.root.table_scans()}
    return prog, scans


@jax.custom_vjp
def gcn_conv(h: jnp.ndarray, edge_keys: jnp.ndarray, edge_w: jnp.ndarray) -> jnp.ndarray:
    """h: (N, D); edge_keys: (E, 2) int32 ⟨src, dst⟩; edge_w: (E,)."""
    prog, _ = _gcn_prog()
    n = h.shape[0]
    env = {
        "Edge": CooRelation(edge_keys, edge_w, (n, n)),
        "Node": DenseRelation(h, 1),
    }
    return session.current().execute(prog.forward, env).data


def _fwd(h, edge_keys, edge_w):
    return gcn_conv(h, edge_keys, edge_w), (h, edge_keys, edge_w)


def _bwd(res, g):
    h, edge_keys, edge_w = res
    prog, scans = _gcn_prog()
    n = h.shape[0]
    edge = CooRelation(edge_keys, edge_w, (n, n))
    node = DenseRelation(h, 1)
    env = {
        "Edge": edge,
        "Node": node,
        f"__fwd_{scans['Edge']}": edge,
        f"__fwd_{scans['Node']}": node,
        "__seed": DenseRelation(g, 1),
    }
    dnode = session.current().execute(prog.grads["Node"], env)
    dedge = session.current().execute(prog.grads["Edge"], env)
    dkeys = np.zeros(edge_keys.shape, dtype=jax.dtypes.float0)
    return dnode.data, dkeys, dedge.values


gcn_conv.defvjp(_fwd, _bwd)
