"""Chunked relation representations for the compiled path (Appendix A).

Two physical layouts, mirroring what a tensor-relational engine stores:

  DenseRelation — the key set is a full grid range(n₀)×…×range(n_{d-1});
      tuples are laid out as one jnp array of shape (n₀,…,n_{d-1}, *chunk).
      This is the layout for blocked matrices/tensors (paper §2.1 Fig 1).

  CooRelation — sparse key set: an int32 key array (nnz, d) plus a value
      array (nnz, *chunk) and per-column extents. This is the layout for
      graph edge relations (paper §1 GCN example).

Both carry ``chunk_rank`` — the number of trailing value ("chunk") dims —
so executors can separate block-key axes from within-chunk axes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: sentinel key component marking padded COO rows (see ``pad_coo_nnz``):
#: every lowering that consumes COO keys drops out-of-range ids, so padded
#: rows contribute nothing to gathers or segment sums.
COO_PAD_KEY = -1


@dataclass
class DenseRelation:
    data: jnp.ndarray
    key_arity: int

    @property
    def chunk_rank(self) -> int:
        return self.data.ndim - self.key_arity

    @property
    def extents(self) -> Tuple[int, ...]:
        return tuple(self.data.shape[: self.key_arity])

    @property
    def chunk_shape(self) -> Tuple[int, ...]:
        return tuple(self.data.shape[self.key_arity:])

    def to_sparse(self) -> dict:
        """Materialize as dict for interpreter cross-checks (small inputs)."""
        out = {}
        arr = np.asarray(self.data)
        for key in np.ndindex(*self.extents):
            v = arr[key]
            out[tuple(int(i) for i in key)] = v if self.chunk_rank else float(v)
        return out


@dataclass
class CooRelation:
    """Sparse relation: ``keys`` (nnz, d) int32 + ``values`` (nnz, *chunk).

    The nnz dimension is the *physical* row axis the distribution planner
    shards over the mesh's data axes (core/planner.py). ``owner_dim`` /
    ``shard_offsets`` describe the optional **owner-partitioned layout**
    produced by ``owner_partition``: rows sorted by the key column
    ``owner_dim`` (the Σ's segment key, e.g. a GCN edge's dst) and padded
    to a shard multiple, with ``shard_offsets[s]`` recording the first
    owner key held by shard ``s``. The layout is what lets the planner
    cost the Σ-over-edges scatter at its edge-cut estimate instead of a
    full all-reduce. Both fields are static schema (pytree aux data) like
    ``extents``; ``None`` means unpartitioned.
    """

    keys: jnp.ndarray    # (nnz, key_arity) int32
    values: jnp.ndarray  # (nnz, *chunk)
    extents: Tuple[int, ...]
    owner_dim: Optional[int] = None
    shard_offsets: Optional[Tuple[int, ...]] = None

    @property
    def key_arity(self) -> int:
        return int(self.keys.shape[1])

    @property
    def nnz(self) -> int:
        return int(self.keys.shape[0])

    @property
    def chunk_rank(self) -> int:
        return self.values.ndim - 1

    @property
    def chunk_shape(self) -> Tuple[int, ...]:
        return tuple(self.values.shape[1:])

    def to_sparse(self) -> dict:
        out = {}
        keys = np.asarray(self.keys)
        vals = np.asarray(self.values)
        for i in range(keys.shape[0]):
            k = tuple(int(x) for x in keys[i])
            v = vals[i]
            out[k] = v if self.chunk_rank else float(v)
        return out


Relation = (DenseRelation, CooRelation)


# ---------------------------------------------------------------------------
# Pytree registration: relations cross jax.jit / shard boundaries as
# containers whose array payloads are leaves and whose relational schema
# (key arity, COO extents) is static aux data. This is what lets the staged
# engine (core/engine.py) jit a whole relation environment and attach
# planner-emitted shardings per relation.
# ---------------------------------------------------------------------------


def _dense_flatten(rel: DenseRelation):
    return (rel.data,), rel.key_arity


def _dense_unflatten(key_arity: int, children) -> DenseRelation:
    (data,) = children
    return DenseRelation(data, key_arity)


def _coo_flatten(rel: CooRelation):
    return (rel.keys, rel.values), (
        rel.extents,
        rel.owner_dim,
        rel.shard_offsets,
    )


def _coo_unflatten(aux, children) -> CooRelation:
    keys, values = children
    extents, owner_dim, shard_offsets = aux
    return CooRelation(keys, values, extents, owner_dim, shard_offsets)


jax.tree_util.register_pytree_node(
    DenseRelation, _dense_flatten, _dense_unflatten
)
jax.tree_util.register_pytree_node(CooRelation, _coo_flatten, _coo_unflatten)


def from_blocked(x, block_shape: Tuple[int, ...]) -> DenseRelation:
    """Split a dense array into a chunked DenseRelation (paper Fig 1)."""
    x = jnp.asarray(x)
    assert x.ndim == len(block_shape)
    grid = []
    for n, b in zip(x.shape, block_shape):
        assert n % b == 0, (n, b)
        grid.append(n // b)
    # (g0,b0,g1,b1,...) -> (g0,g1,...,b0,b1,...)
    shape = []
    for g, b in zip(grid, block_shape):
        shape += [g, b]
    y = x.reshape(shape)
    perm = list(range(0, 2 * len(grid), 2)) + list(range(1, 2 * len(grid), 2))
    return DenseRelation(jnp.transpose(y, perm), key_arity=len(grid))


def to_blocked(rel: DenseRelation):
    """Inverse of from_blocked: reassemble the dense array."""
    d = rel.key_arity
    grid = rel.extents
    block = rel.chunk_shape
    assert len(block) == d, "to_blocked requires chunk_rank == key_arity"
    perm = [None] * (2 * d)
    for i in range(d):
        perm[2 * i] = i
        perm[2 * i + 1] = d + i
    y = jnp.transpose(rel.data, perm)
    return y.reshape(tuple(g * b for g, b in zip(grid, block)))


def scalar_relation(value=1.0, dtype=jnp.float32) -> DenseRelation:
    """The one-tuple relation {(⟨⟩, value)} — loss outputs / gradient seeds."""
    return DenseRelation(jnp.asarray(value, dtype=dtype), key_arity=0)


# ---------------------------------------------------------------------------
# COO nnz-dimension layouts (the sharded-graph fast path)
# ---------------------------------------------------------------------------


def pad_coo_nnz(rel: CooRelation, target_nnz: int) -> CooRelation:
    """Pad the nnz axis up to ``target_nnz`` rows with ``COO_PAD_KEY`` keys
    and zero values — the pad-and-mask layout the engine emits when a
    planned nnz sharding does not divide the row count. Padded rows are
    inert: every key column is out of range, so gathers mask them to zero
    and segment sums drop them."""
    pad = target_nnz - rel.nnz
    if pad < 0:
        raise ValueError(
            f"pad_coo_nnz: target {target_nnz} < nnz {rel.nnz}"
        )
    if pad == 0:
        return rel
    keys = jnp.pad(rel.keys, ((0, pad), (0, 0)), constant_values=COO_PAD_KEY)
    values = jnp.pad(
        rel.values, ((0, pad),) + ((0, 0),) * rel.chunk_rank
    )
    return CooRelation(keys, values, rel.extents, rel.owner_dim, rel.shard_offsets)


def measure_stats(rel):
    """Measure a relation's key-domain statistics — the
    ``planner.RelationStats`` a ``Database`` catalog tracks per table and
    refreshes on ``put``.

    DenseRelation key sets are full grids, so every statistic is exact
    (distinct = extents, density = 1, and each histogram bucket holds
    its share of the uniform grid), but not free: the histograms are
    taken on the host with ``np.histogram`` over each key range. On the
    host of a TPU v5e, refreshing an 8,192 × 2,000 batch, its 8,192
    labels and a 2,000-vector took 0.80 ms together, 40 % of a 2.0 ms
    logistic-regression step (the ``repro/session.stats`` span;
    ``put(..., refresh_stats=False)`` skips the pass when only values
    changed). CooRelation key columns are counted with ``np.unique`` /
    ``np.histogram`` over the live (non-padded) rows — a host-side pass
    over concrete key arrays, i.e. a data-loading step like
    ``owner_partition``, never a traced one."""
    from .planner import HIST_BUCKETS, RelationStats

    def column_hist(values, extent, per_value=1):
        """Equi-width tuple counts over ``[0, extent)``."""
        if extent <= 0:
            return tuple([0] * HIST_BUCKETS)
        counts, _ = np.histogram(
            values, bins=HIST_BUCKETS, range=(0, extent)
        )
        return tuple(int(c) * int(per_value) for c in counts)

    if isinstance(rel, DenseRelation):
        extents = rel.extents
        size = 1
        for e in extents:
            size *= int(e)
        hist = tuple(
            column_hist(
                np.arange(int(e)), int(e), size // int(e) if int(e) else 0
            )
            for e in extents
        )
        return RelationStats(
            distinct=tuple(int(e) for e in extents),
            extents=tuple(int(e) for e in extents),
            nnz=size,
            density=1.0,
            hist=hist,
        )
    if isinstance(rel, CooRelation):
        keys = np.asarray(rel.keys)
        live = keys[keys[:, 0] != COO_PAD_KEY] if keys.size else keys
        nnz = int(live.shape[0])
        distinct = tuple(
            int(np.unique(live[:, j]).size) if nnz else 0
            for j in range(rel.key_arity)
        )
        size = 1
        for e in rel.extents:
            size *= int(e)
        hist = tuple(
            column_hist(live[:, j], int(rel.extents[j]))
            for j in range(rel.key_arity)
        )
        return RelationStats(
            distinct=distinct,
            extents=tuple(int(e) for e in rel.extents),
            nnz=nnz,
            density=(nnz / size) if size else 0.0,
            hist=hist,
        )
    raise TypeError(f"measure_stats: not a relation: {type(rel)}")


# ---------------------------------------------------------------------------
# Chunk manifests: the host-resident blocked layout for out-of-core waves
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ChunkManifest:
    """Row-blocking of one relation for out-of-core execution.

    ``axis`` is the blocked dimension — a key dim for a DenseRelation, and
    always the physical nnz row axis for a CooRelation. ``boundaries`` is
    the monotone cut vector (num_chunks+1 entries, first 0, last the row
    count), so chunk ``w`` is rows ``[boundaries[w], boundaries[w+1])``.
    ``owner_aligned`` records that COO cuts were snapped to owner-run
    starts (see ``make_manifest``): no Σ segment then straddles a wave, so
    each wave's partial segment grid is exact where touched and the
    ⊕-unit elsewhere — what lets zero-preserving kernels stream."""

    axis: int
    boundaries: Tuple[int, ...]
    owner_aligned: bool = False

    @property
    def num_chunks(self) -> int:
        return len(self.boundaries) - 1

    def chunk_rows(self, w: int) -> int:
        return self.boundaries[w + 1] - self.boundaries[w]

    @property
    def max_rows(self) -> int:
        return max(
            self.boundaries[w + 1] - self.boundaries[w]
            for w in range(self.num_chunks)
        )


def make_manifest(rel, num_chunks: int, axis: int = 0) -> ChunkManifest:
    """Block ``rel`` into ``num_chunks`` row ranges.

    Dense relations split a key dim evenly (remainder spread over the
    leading chunks). COO relations split the nnz axis; when the relation
    is owner-partitioned, tentative even cuts are snapped *down* to the
    start of the owner run they fall into, so one Σ segment is never split
    across two waves (duplicate cuts collapse — heavy owners can reduce
    the chunk count)."""
    if num_chunks < 1:
        raise ValueError(f"make_manifest: num_chunks={num_chunks} must be >= 1")
    if isinstance(rel, DenseRelation):
        if not 0 <= axis < rel.key_arity:
            raise ValueError(
                f"make_manifest: axis {axis} out of range for key arity "
                f"{rel.key_arity}"
            )
        rows = int(rel.extents[axis])
    elif isinstance(rel, CooRelation):
        axis = 0
        rows = rel.nnz
    else:
        raise TypeError(f"make_manifest: not a relation: {type(rel)}")
    if num_chunks > max(rows, 1):
        raise ValueError(
            f"make_manifest: {num_chunks} chunks over {rows} rows"
        )
    base, rem = divmod(rows, num_chunks)
    cuts = [0]
    for w in range(num_chunks):
        cuts.append(cuts[-1] + base + (1 if w < rem else 0))
    owner_aligned = False
    if isinstance(rel, CooRelation) and rel.owner_dim is not None and rows:
        owners = np.asarray(rel.keys)[:, rel.owner_dim]
        # first row of each contiguous owner run; in the owner-sorted live
        # region runs ARE owner groups, and the trailing COO_PAD_KEY pad
        # rows form one final run of their own (splitting pads is harmless)
        starts = np.flatnonzero(np.r_[True, owners[1:] != owners[:-1]])
        snapped = [0]
        for t in cuts[1:-1]:
            s = int(starts[np.searchsorted(starts, t, side="right") - 1])
            if s > snapped[-1]:
                snapped.append(s)
        snapped.append(rows)
        cuts = snapped
        owner_aligned = True
    return ChunkManifest(axis, tuple(cuts), owner_aligned)


def split_chunks(rel, manifest: ChunkManifest):
    """Materialize the manifest's chunks as host-resident relations
    (numpy payloads — this is the spill step, not a traced one)."""
    out = []
    for w in range(manifest.num_chunks):
        lo, hi = manifest.boundaries[w], manifest.boundaries[w + 1]
        if isinstance(rel, DenseRelation):
            data = np.asarray(rel.data)
            sl = [slice(None)] * data.ndim
            sl[manifest.axis] = slice(lo, hi)
            out.append(DenseRelation(data[tuple(sl)], rel.key_arity))
        else:
            out.append(
                CooRelation(
                    np.asarray(rel.keys)[lo:hi],
                    np.asarray(rel.values)[lo:hi],
                    rel.extents,
                    rel.owner_dim,
                    None,
                )
            )
    return out


def assemble_chunks(chunks, manifest: ChunkManifest):
    """Inverse of ``split_chunks``: reassemble one relation."""
    if not chunks:
        raise ValueError("assemble_chunks: no chunks")
    first = chunks[0]
    if isinstance(first, DenseRelation):
        data = np.concatenate(
            [np.asarray(c.data) for c in chunks], axis=manifest.axis
        )
        return DenseRelation(data, first.key_arity)
    keys = np.concatenate([np.asarray(c.keys) for c in chunks], axis=0)
    values = np.concatenate([np.asarray(c.values) for c in chunks], axis=0)
    return CooRelation(keys, values, first.extents, first.owner_dim, None)


def rechunk(chunks, old: ChunkManifest, new: ChunkManifest):
    """Re-block a chunked relation from manifest ``old`` to ``new`` —
    the same all-to-all ``split ∘ assemble`` whether the target is a
    different grid or a different tier. Round-tripping A→B→A is
    bit-stable (pure row movement, no arithmetic)."""
    if old.boundaries[-1] != new.boundaries[-1]:
        raise ValueError(
            f"rechunk: row counts differ ({old.boundaries[-1]} vs "
            f"{new.boundaries[-1]})"
        )
    if old.axis != new.axis:
        raise ValueError(f"rechunk: axes differ ({old.axis} vs {new.axis})")
    return split_chunks(assemble_chunks(chunks, old), new)


def _stable_order(col: np.ndarray) -> np.ndarray:
    """``np.argsort(col, kind="stable")``. Keys in ``[0, 2**32)`` take two
    16-bit radix passes, least significant first, which numpy sorts in
    linear time: tens of millions of edges in a second or two, not in
    tens of seconds."""
    if col.size == 0 or col.min() < 0 or col.max() >= 2**32:
        return np.argsort(col, kind="stable")
    col = col.astype(np.uint32)
    order = np.argsort(col.astype(np.uint16), kind="stable")
    hi = (col[order] >> 16).astype(np.uint16)
    return order[np.argsort(hi, kind="stable")]


def owner_partition(
    rel: CooRelation, num_shards: int, dim: int = -1
) -> CooRelation:
    """Owner-partitioned nnz layout: sort rows by the key column ``dim``
    (the Σ's segment key — a GCN edge's dst node), pad nnz to a multiple
    of ``num_shards``, and record per-shard segment offsets.

    Under an nnz sharding over ``num_shards`` devices, each equal shard of
    the sorted rows then holds a contiguous owner-key range
    (``shard_offsets[s]`` is the first owner key of shard ``s``; a shard
    whose rows are all padding owns no segments and records the
    one-past-the-end owner extent), so the Σ-by-owner scatter is local
    except at range boundaries — the layout the planner's edge-cut
    estimate (``planner.EDGE_CUT_LOCAL``) prices. Sorting happens on the
    host (numpy): this is a data-loading step, not a traced one."""
    if num_shards < 1:
        raise ValueError(f"owner_partition: num_shards={num_shards} must be >= 1")
    dim = dim % rel.key_arity
    keys = np.asarray(rel.keys)
    values = np.asarray(rel.values)
    order = _stable_order(keys[:, dim])
    keys, values = keys[order], values[order]
    sorted_rel = CooRelation(
        jnp.asarray(keys),
        jnp.asarray(values),
        rel.extents,
        owner_dim=dim,
    )
    padded_nnz = ((sorted_rel.nnz + num_shards - 1) // num_shards) * num_shards
    sorted_rel = pad_coo_nnz(sorted_rel, padded_nnz)
    per = padded_nnz // num_shards
    owners = keys[:, dim]
    end = int(rel.extents[dim])  # empty-shard sentinel: one past the last owner
    offsets = tuple(
        int(owners[s * per]) if s * per < len(owners) else end
        for s in range(num_shards)
    )
    return CooRelation(
        sorted_rel.keys,
        sorted_rel.values,
        rel.extents,
        owner_dim=dim,
        shard_offsets=offsets,
    )
