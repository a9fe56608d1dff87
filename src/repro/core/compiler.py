"""Chunked compiler: lowers FRA query graphs to jit-able JAX computations.

This is the fast path of the engine. Where the sparse interpreter executes
tuple-at-a-time (the oracle), this executor lowers whole operators to XLA
ops over chunked relations:

  Σ(grp, +, ⋈(eq-pred, proj, mul/matmul, ·, ·)) over DenseRelations
      → one ``jnp.einsum`` (block axes from the join's key-equivalence
        classes, chunk axes from the kernel's chunk_spec);
  Σ(grp, +, ⋈(COO, Dense)) (graph convolution and its gradient)
      → one fused gather → ⊗ → ``segment_sum`` over blocks of nnz rows
        (``_coo_join_agg``): no nnz × width array beyond one block, and
        per shard inside ``shard_map`` with the partial sums
        reduce-scattered (``exchange``) where the plan shards the nnz rows;
  joins against a CooRelation (graph edges)   → gather (``take``);
  Σ over a CooRelation                        → ``segment_sum`` (scatter-add);
  RJP broadcast/aligned joins (from Σ/σ differentiation)
      → transpose + broadcast + elementwise kernel;
  σ                                           → slice/transpose/elementwise.

Everything here traces under ``jax.jit``; the paper's "database query
optimizer distributes the computation" role is then played by the sharding
planner (planner.py — 2-D (data × model) plans on a launch/mesh mesh) +
the XLA SPMD partitioner, which inserts the chosen plan's model-axis
psum and data-axis batch collectives around the lowerings emitted here.

The three hardware hot-spots — the Σ over a CooRelation, the matmul-shaped
Σ∘⋈ einsum, and the COO gather join (edge ⋈ node, plus the restricted-join
sparse-gradient gather) — are not called directly: each lowering site is
resolved against the kernel dispatch registry (kernels.py), which routes
it to the Pallas TPU kernels (kernels/segsum, kernels/matmul,
kernels/gather), their interpret/ref CPU tiers, or the default jnp path,
according to the ``DispatchTable`` the engine threads through
``_execute_graph``. Resolved tiers are recorded into the caller's
``resolutions`` dict (the engine exposes them on ``Compiled.resolutions``).
All gather/scatter sites honour the COO pad-and-mask contract: negative
(padding) key components gather zero rows and are dropped by segment sums,
so an nnz axis padded up to a shard multiple stays numerically inert.

Dense gradients of *absent* tuples: a relational gradient relation simply
lacks tuples that received no contribution; a dense array cannot express
absence, so the compiled gradient stores explicit zeros there. Under the
additive aggregation semantics this is exact.
"""

from __future__ import annotations

import dataclasses
import math
import string
from typing import Any, Dict, FrozenSet, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import fra, kernels
from .kernels import BinKernel
from .keys import In, JoinPred, JoinProj, KeyFn, L, Lit, R, join_equiv_classes
from .relation import COO_PAD_KEY, CooRelation, DenseRelation
from .spans import EXCHANGE, JOIN_AGG

AnyRel = Union[DenseRelation, CooRelation]
Env = Dict[str, AnyRel]

_BLOCK_LETTERS = string.ascii_uppercase


def _vmapped(fn, times: int):
    """Kernel functions have chunk-local semantics (they see one tuple's
    value). Lift them over leading block-key / nnz axes with vmap so
    shape-dependent kernels (e.g. sum_chunk, per-chunk softmax) stay
    correct; XLA fuses the trivial elementwise cases back to one op."""
    for _ in range(times):
        fn = jax.vmap(fn)
    return fn


class LoweringError(NotImplementedError):
    pass


def _norm_pairs(pred: JoinPred):
    """Normalize eq pairs into (L, R), (L, Lit), (R, Lit) canonical forms."""
    lr, llit, rlit = [], [], []
    for a, b in pred.eqs:
        pair = (a, b)
        if isinstance(b, L) or (isinstance(b, R) and isinstance(a, Lit)):
            pair = (b, a)
        a, b = pair
        if isinstance(a, L) and isinstance(b, R):
            lr.append((a.idx, b.idx))
        elif isinstance(a, R) and isinstance(b, L):
            lr.append((b.idx, a.idx))
        elif isinstance(a, L) and isinstance(b, Lit):
            llit.append((a.idx, b.val))
        elif isinstance(a, R) and isinstance(b, Lit):
            rlit.append((a.idx, b.val))
        elif isinstance(a, L) and isinstance(b, L):
            raise LoweringError(f"L-L equality {a}=={b} not lowerable")
        elif isinstance(a, R) and isinstance(b, R):
            raise LoweringError(f"R-R equality {a}=={b} not lowerable")
        else:
            raise LoweringError(f"cannot normalize predicate pair {a}=={b}")
    return lr, llit, rlit


# ---------------------------------------------------------------------------
# Join lowering: einsum path (dense ⋈ dense, multiplicative kernel)
# ---------------------------------------------------------------------------


def _note(
    resolutions: Optional[Dict], op: str, site: str, impl, info: Optional[Dict] = None
) -> None:
    """Record a dispatch decision for diagnostics (Compiled.resolutions):
    ``impl`` is the resolved ``KernelImpl``, or the tier name of a
    composite site (the fused join-aggregate), which records no info.
    Distinct sites that share a shape signature get ordinal suffixes
    (``op[site]#2`` …) so the record counts every decision, not every
    distinct shape. When ``resolutions`` is a ``kernels.ResolutionLog``
    (the engine's lowering walk) the site-info dict is snapshotted too,
    so ``analysis.kernelcheck`` can replay the resolution and prove it
    stable across retraces."""
    if resolutions is None:
        return
    key = f"{op}[{site}]"
    if key in resolutions:
        i = 2
        while f"{key}#{i}" in resolutions:
            i += 1
        key = f"{key}#{i}"
    tier = getattr(impl, "tier", impl)
    resolutions[key] = tier
    if info is not None and hasattr(resolutions, "record"):
        resolutions.record(key, op, site, tier, dict(info))


def _dispatched_matmul_join(
    lspec: str,
    rspec: str,
    ospec: str,
    kernel: BinKernel,
    lrel: DenseRelation,
    rrel: DenseRelation,
    dispatch,
    resolutions: Optional[Dict],
) -> Optional[DenseRelation]:
    """Route a matmul-shaped Σ∘⋈ einsum through the ``blocked_matmul``
    dispatch op: contractions expressible as ONE 2-D matmul after
    flattening block axes — the MatMul chunk kernel ('mk','kn'→'mn') or a
    chunkless elementwise ⊗ — with every contracted block class shared by
    both sides and no batch class. Returns None to fall back to
    ``jnp.einsum`` (including when the table resolves this site to the
    jnp tier, which *is* the einsum path)."""
    if kernel.chunk_spec is not None:
        if kernel.chunk_spec != ("mk", "kn", "mn"):
            return None
        chunked = True
    elif kernel.elementwise and lrel.chunk_rank == 0 and rrel.chunk_rank == 0:
        chunked = False
    else:
        return None
    sl, sr, so = set(lspec), set(rspec), set(ospec)
    if len(sl) != len(lspec) or len(sr) != len(rspec) or len(so) != len(ospec):
        return None                  # repeated block class within one spec
    con = [c for c in lspec if c in sr and c not in so]
    if not con and not chunked:
        return None                  # outer product: nothing to win
    if (sl & sr) - set(con):
        return None                  # batch class (in both inputs + output)
    if (sl - set(con)) - so or (sr - set(con)) - so or so - (sl | sr):
        return None                  # unilateral sum / phantom output class

    l_keep = [c for c in lspec if c in so]
    r_keep = [c for c in rspec if c in so]
    la, ra = len(lspec), len(rspec)
    lext = {c: lrel.data.shape[lspec.index(c)] for c in lspec}
    rext = {c: rrel.data.shape[rspec.index(c)] for c in rspec}

    m, kk, n = (
        (lrel.data.shape[la], lrel.data.shape[la + 1], rrel.data.shape[ra + 1])
        if chunked
        else (1, 1, 1)
    )
    rows = math.prod(lext[c] for c in l_keep) * m
    inner = math.prod(lext[c] for c in con) * kk
    cols = math.prod(rext[c] for c in r_keep) * n

    ct = jnp.result_type(lrel.data, rrel.data)
    info = {"m": rows, "k": inner, "n": cols, "dtype": ct}
    impl = kernels.resolve_impl("blocked_matmul", info, dispatch)
    _note(resolutions, "blocked_matmul", f"m={rows},k={inner},n={cols}", impl, info)
    if impl.tier == "jnp":
        return None                  # the einsum below IS the jnp tier

    lk_ax = [lspec.index(c) for c in l_keep]
    lc_ax = [lspec.index(c) for c in con]
    rk_ax = [rspec.index(c) for c in r_keep]
    rc_ax = [rspec.index(c) for c in con]
    if chunked:
        lperm = lk_ax + [la] + lc_ax + [la + 1]      # (keep.., m, con.., k)
        rperm = rc_ax + [ra] + rk_ax + [ra + 1]      # (con.., k, keep.., n)
    else:
        lperm = lk_ax + lc_ax
        rperm = rc_ax + rk_ax
    l2 = jnp.transpose(lrel.data.astype(ct), lperm).reshape(rows, inner)
    r2 = jnp.transpose(rrel.data.astype(ct), rperm).reshape(inner, cols)
    out2 = impl.fn(l2, r2)

    shp = tuple(lext[c] for c in l_keep) + ((m,) if chunked else ())
    shp += tuple(rext[c] for c in r_keep) + ((n,) if chunked else ())
    out = out2.reshape(shp)
    # natural axis order: l_keep.., [m], r_keep.., [n] → ospec order + chunks
    ax_of = {c: i for i, c in enumerate(l_keep)}
    off = len(l_keep) + (1 if chunked else 0)
    for j, c in enumerate(r_keep):
        ax_of[c] = off + j
    perm = [ax_of[c] for c in ospec]
    if chunked:
        perm += [len(l_keep), off + len(r_keep)]
    out = jnp.transpose(out, perm)
    return DenseRelation(out, key_arity=len(ospec))


def _einsum_join(
    join: fra.Join,
    grp: Optional[KeyFn],
    lrel: DenseRelation,
    rrel: DenseRelation,
    dispatch=None,
    resolutions: Optional[Dict] = None,
) -> DenseRelation:
    la, ra = join.left.key_arity, join.right.key_arity
    uf = join_equiv_classes(join.pred, la, ra)
    for a, b in join.pred.eqs:
        if isinstance(a, Lit) or isinstance(b, Lit):
            raise LoweringError("literal in einsum-join predicate")

    letters: Dict[object, str] = {}

    def letter(comp) -> str:
        root = uf.find(comp)
        if root not in letters:
            letters[root] = _BLOCK_LETTERS[len(letters)]
        return letters[root]

    lspec = "".join(letter(L(i)) for i in range(la))
    rspec = "".join(letter(R(j)) for j in range(ra))

    out_comps: List = list(join.proj.comps)
    if grp is not None:
        composed = []
        for c in grp.comps:
            if isinstance(c, Lit):
                raise LoweringError("Lit in grp over einsum join")
            composed.append(join.proj.comps[c.idx])
        out_comps = composed
    if any(isinstance(c, Lit) for c in out_comps):
        raise LoweringError("Lit in einsum join projection")
    ospec = "".join(letter(c) for c in out_comps)

    if grp is None:
        # A bare join must not implicitly aggregate: every block class must
        # survive into the output key.
        if not set(lspec + rspec) <= set(ospec):
            raise LoweringError(
                "bare join drops a key class (duplicate keys); wrap in Σ"
            )

    k = join.kernel
    if k.chunk_spec is not None:
        lc, rc, oc = k.chunk_spec
        if len(lc) != lrel.chunk_rank or len(rc) != rrel.chunk_rank:
            raise LoweringError(
                f"chunk rank mismatch for {k.name}: {lrel.chunk_rank},{rrel.chunk_rank}"
            )
    elif k.elementwise:
        cr = max(lrel.chunk_rank, rrel.chunk_rank)
        oc = string.ascii_lowercase[:cr]
        lc = oc[cr - lrel.chunk_rank:]
        rc = oc[cr - rrel.chunk_rank:]
    else:
        raise LoweringError(f"kernel {k.name} is not einsum-lowerable")

    routed = _dispatched_matmul_join(
        lspec, rspec, ospec, k, lrel, rrel, dispatch, resolutions
    )
    if routed is not None:
        return routed

    spec = f"{lspec}{lc},{rspec}{rc}->{ospec}{oc}"
    data = jnp.einsum(spec, lrel.data, rrel.data)
    return DenseRelation(data, key_arity=len(out_comps))


# ---------------------------------------------------------------------------
# Join lowering: aligned/broadcast path (RJPs of σ and Σ, pointwise losses)
# ---------------------------------------------------------------------------


def _aligned_join(
    join: fra.Join, lrel: DenseRelation, rrel: DenseRelation
) -> Optional[DenseRelation]:
    """Joins whose projection is the identity on one side: the other side is
    permuted/broadcast into that side's grid and the kernel applied
    pointwise. Covers the RJP-of-Σ broadcast join, the RJP-of-σ join, and
    pointwise losses (⊗ against labels with proj → keyL)."""
    la, ra = join.left.key_arity, join.right.key_arity
    lr, llit, rlit = _norm_pairs(join.pred)

    id_over_R = join.proj.comps == tuple(R(j) for j in range(ra))
    id_over_L = join.proj.comps == tuple(L(i) for i in range(la))
    if id_over_R:
        base_rel, base_arity = rrel, ra
        mapped_rel, mapped_arity = lrel, la
        pairs = [(i, j) for i, j in lr]          # mapped comp i ↔ base comp j
        mapped_lit, base_lit = llit, rlit
        order = "lr"
    elif id_over_L:
        base_rel, base_arity = lrel, la
        mapped_rel, mapped_arity = rrel, ra
        pairs = [(j, i) for i, j in lr]
        mapped_lit, base_lit = rlit, llit
        order = "rl"
    else:
        return None

    m2b = dict(pairs)
    if len(m2b) != len(pairs) or len(set(m2b.values())) != len(m2b):
        return None
    if len(m2b) != mapped_arity or mapped_lit:
        return None  # a mapped axis is unconstrained -> would need summation

    # Permute mapped block axes into base-axis order, insert broadcast axes.
    src = mapped_rel.data
    perm = sorted(range(mapped_arity), key=lambda i: m2b[i])
    src = jnp.transpose(
        src, tuple(perm) + tuple(range(mapped_arity, src.ndim))
    )
    matched_base = set(m2b.values())
    for j in range(base_arity):
        if j not in matched_base:
            src = jnp.expand_dims(src, axis=j)
    # src now has base_arity block axes (some size-1) + mapped chunk dims;
    # broadcast explicitly so pointwise kernels that ignore one operand
    # (e.g. the Σ-RJP's take_l) still produce full-grid outputs.
    src = jnp.broadcast_to(
        src, base_rel.extents + tuple(src.shape[base_arity:])
    )

    bb = base_rel.data
    kfn = _vmapped(join.kernel.fn, base_arity)
    if order == "lr":
        val = kfn(src, bb)
    else:
        val = kfn(bb, src)

    out_arity = base_arity
    if base_lit:
        idx = jnp.ones(base_rel.extents, dtype=bool)
        for j, v in base_lit:
            ax_shape = [1] * base_arity
            ax_shape[j] = base_rel.extents[j]
            m = (jnp.arange(base_rel.extents[j]) == v).reshape(ax_shape)
            idx = idx & m
        mask = idx.reshape(idx.shape + (1,) * (val.ndim - out_arity))
        val = jnp.where(mask, val, jnp.zeros((), dtype=val.dtype))
    return DenseRelation(val, key_arity=out_arity)


def _broadcast_join(
    join: fra.Join,
    grp: Optional[KeyFn],
    lrel: DenseRelation,
    rrel: DenseRelation,
) -> Optional[DenseRelation]:
    """Last-resort dense ⋈ dense lowering for kernels with no einsum hints
    and non-aligned projections (e.g. the autodiff general path's inner
    join under a merging Σ): materialize the joint key-class grid,
    broadcast both operands into it, apply the kernel pointwise, and sum
    out the classes the (grp-composed) output key drops. Cost is the full
    class-grid product — the paper's *unoptimized* RJP — so the einsum and
    aligned paths are always tried first."""
    la, ra = join.left.key_arity, join.right.key_arity
    for a, b in join.pred.eqs:
        if isinstance(a, Lit) or isinstance(b, Lit):
            return None
    uf = join_equiv_classes(join.pred, la, ra)

    out_comps: List = list(join.proj.comps)
    if grp is not None:
        composed = []
        for c in grp.comps:
            if isinstance(c, Lit):
                return None
            composed.append(join.proj.comps[c.idx])
        out_comps = composed
    if any(isinstance(c, Lit) for c in out_comps):
        return None

    # one grid axis per join equivalence class, first-appearance order
    ax_of: Dict[object, int] = {}
    extents: List[int] = []
    lcomps = tuple(L(i) for i in range(la))
    rcomps = tuple(R(j) for j in range(ra))
    for comps, rel in ((lcomps, lrel), (rcomps, rrel)):
        for k, c in enumerate(comps):
            root = uf.find(c)
            if root not in ax_of:
                ax_of[root] = len(extents)
                extents.append(rel.extents[k])
    out_ax: List[int] = []
    for c in out_comps:
        ax = ax_of[uf.find(c)]
        if ax in out_ax:
            return None          # repeated class in output key (diagonal)
        out_ax.append(ax)
    if grp is None and len(out_ax) != len(extents):
        # a bare join dropping a class would emit duplicate keys
        return None

    def into_grid(rel: DenseRelation, comps) -> jnp.ndarray:
        axes = [ax_of[uf.find(c)] for c in comps]
        if len(set(axes)) != len(axes):
            return None          # intra-side equality (diagonal operand)
        perm = sorted(range(len(axes)), key=lambda i: axes[i])
        data = jnp.transpose(
            rel.data, tuple(perm) + tuple(range(len(axes), rel.data.ndim))
        )
        present = set(axes)
        for ax in range(len(extents)):
            if ax not in present:
                data = jnp.expand_dims(data, axis=ax)
        return jnp.broadcast_to(data, tuple(extents) + rel.chunk_shape)

    lb = into_grid(lrel, lcomps)
    rb = into_grid(rrel, rcomps)
    if lb is None or rb is None:
        return None
    val = _vmapped(join.kernel.fn, len(extents))(lb, rb)
    drop = tuple(ax for ax in range(len(extents)) if ax not in out_ax)
    if drop:
        val = jnp.sum(val, axis=drop)
    remaining = [ax for ax in range(len(extents)) if ax not in drop]
    perm = [remaining.index(ax) for ax in out_ax]
    val = jnp.transpose(
        val, tuple(perm) + tuple(range(len(out_ax), val.ndim))
    )
    return DenseRelation(val, key_arity=len(out_comps))


# ---------------------------------------------------------------------------
# Join lowering: gather path (one side COO)
# ---------------------------------------------------------------------------


def _dispatched_gather(
    dense: DenseRelation,
    idx_cols: Tuple[jnp.ndarray, ...],
    dispatch,
    resolutions: Optional[Dict],
) -> jnp.ndarray:
    """Gather rows of ``dense`` at per-key-dim index columns through the
    ``gather_join`` dispatch op: the key grid is flattened to one row axis
    and the chunk to one feature axis, matching the op contract
    ``fn(table2d, rows) → table2d[rows]`` (out-of-range / negative ids —
    the COO nnz padding — yield zero rows). Returns (E, *chunk)."""
    assert len(idx_cols) == dense.key_arity and dense.key_arity > 0
    e = idx_cols[0].shape[0]
    chunk = dense.chunk_shape
    if e == 0:
        # zero-nnz COO guard: every tier agrees on the empty gather
        return jnp.zeros((0,) + chunk, dtype=dense.data.dtype)
    # flat row ids; any out-of-range component poisons the row to -1 so
    # the kernel's mask drops it
    valid = None
    flat = jnp.zeros((e,), dtype=jnp.int32)
    for ext, col in zip(dense.extents, idx_cols):
        col = col.astype(jnp.int32)
        ok = (col >= 0) & (col < ext)
        valid = ok if valid is None else (valid & ok)
        flat = flat * ext + jnp.clip(col, 0, max(ext - 1, 0))
    rows = jnp.where(valid, flat, jnp.int32(-1))
    n = math.prod(dense.extents)
    d = math.prod(chunk)
    info = {"rows": e, "num_rows": n, "dim": d, "dtype": dense.data.dtype}
    impl = kernels.resolve_impl("gather_join", info, dispatch)
    _note(resolutions, "gather_join", f"E={e},N={n},D={d}", impl, info)
    table2 = dense.data.reshape(n, d)
    return impl.fn(table2, rows).reshape((e,) + chunk)


def _mask_padded_rows(keys: jnp.ndarray, vals: jnp.ndarray) -> jnp.ndarray:
    """Zero value rows whose key carries a negative (padding) component, so
    padded nnz rows stay inert through non-multiplicative kernels too."""
    valid = jnp.all(keys >= 0, axis=1)
    return jnp.where(
        valid.reshape((-1,) + (1,) * (vals.ndim - 1)),
        vals,
        jnp.zeros((), dtype=vals.dtype),
    )


def _coo_operands(join: fra.Join, lrel: AnyRel, rrel: AnyRel):
    """``(coo, dense, coo_is_left, d2c)`` of a COO ⋈ Dense join: ``d2c``
    maps each dense key component to the COO key column it is gathered
    at."""
    coo_is_left = isinstance(lrel, CooRelation)
    coo = lrel if coo_is_left else rrel
    dense = rrel if coo_is_left else lrel
    assert isinstance(dense, DenseRelation)
    lr, llit, rlit = _norm_pairs(join.pred)
    if llit or rlit:
        raise LoweringError("literal predicates on COO joins not supported")
    # (coo column ↔ dense comp) pairs
    if coo_is_left:
        pairs = [(i, j) for i, j in lr]
    else:
        pairs = [(j, i) for i, j in lr]
    d2c = {j: i for i, j in pairs}
    if len(d2c) != dense.key_arity:
        raise LoweringError(
            "COO join requires every dense key component matched (gather)"
        )
    return coo, dense, coo_is_left, d2c


def _coo_join(
    join: fra.Join, lrel: AnyRel, rrel: AnyRel, dispatch, resolutions
) -> CooRelation:
    coo, dense, coo_is_left, d2c = _coo_operands(join, lrel, rrel)
    idx = tuple(coo.keys[:, d2c[j]] for j in range(dense.key_arity))
    gathered = _dispatched_gather(dense, idx, dispatch, resolutions)
    kfn = _vmapped(join.kernel.fn, 1)
    if coo_is_left:
        vals = kfn(coo.values, gathered)
    else:
        vals = kfn(gathered, coo.values)
    vals = _mask_padded_rows(coo.keys, vals)

    cols = []
    extents = []
    for c in join.proj.comps:
        if isinstance(c, Lit):
            cols.append(jnp.full((coo.nnz,), c.val, dtype=coo.keys.dtype))
            extents.append(c.val + 1)
            continue
        if coo_is_left:
            col = c.idx if isinstance(c, L) else d2c[c.idx]
            ext = coo.extents[c.idx] if isinstance(c, L) else dense.extents[c.idx]
        else:
            col = c.idx if isinstance(c, R) else d2c[c.idx]
            ext = coo.extents[c.idx] if isinstance(c, R) else dense.extents[c.idx]
        cols.append(coo.keys[:, col])
        extents.append(ext)
    keys = jnp.stack(cols, axis=1) if cols else jnp.zeros((coo.nnz, 0), coo.keys.dtype)
    return CooRelation(keys, vals, tuple(extents))


# ---------------------------------------------------------------------------
# Fused join-aggregate: Σ(COO ⋈ Dense) in blocks of nnz rows, per shard
# ---------------------------------------------------------------------------

#: The most bytes of messages (nnz rows × the width of ⊗'s output) that
#: one block of a fused join-aggregate materialises. A call whose whole
#: message array fits is lowered as one gather, ⊗ and Σ; a larger one
#: loops over blocks of nnz rows. At ogbn-arxiv shape the largest call,
#: 1,335,586 edges × 256 × 4 B = 1.37 GB, is one block.
JOIN_AGG_BLOCK_BYTES = 1_400_000_000

#: A block's nnz rows are a multiple of this (the row gather's launch).
BLOCK_ROWS_QUANTUM = 65_536

#: A row-sharded table's rows a shard are a multiple of this (whole TPU
#: tiles of any dtype).
TABLE_ROWS_QUANTUM = 128


@dataclasses.dataclass(frozen=True)
class NnzShards:
    """The environment's COO relations, by name, whose nnz rows the plan
    puts on the data ``axes`` of ``mesh`` (``data:shard_nnz_*``). Their
    join-aggregates run per shard inside ``shard_map``; the partial sums
    meet in ``exchange``."""

    mesh: Any
    axes: Tuple[str, ...]
    names: FrozenSet[str]

    @property
    def size(self) -> int:
        return math.prod(int(self.mesh.shape[a]) for a in self.axes)

    def spec(self, ndim: int) -> P:
        """Rows over the data axes, the other ``ndim - 1`` dims whole."""
        ax = self.axes if len(self.axes) > 1 else self.axes[0]
        return P(ax, *([None] * (ndim - 1)))


def exchange(partial: jnp.ndarray, axes: Tuple[str, ...], *, scatter: bool) -> jnp.ndarray:
    """Combine the shards' partial sums of a join-aggregate over the data
    ``axes``, inside ``shard_map``: a reduce-scatter of the rows, so each
    shard owns its contiguous 1/n of them, or an all-reduce where the
    output is replicated (a Σ with no output key). Every partial sum that
    crosses chips goes through here."""
    with jax.named_scope(EXCHANGE):
        if scatter:
            return jax.lax.psum_scatter(partial, axes, scatter_dimension=0, tiled=True)
        return jax.lax.psum(partial, axes)


def _dispatched_segment_sum(
    values: jnp.ndarray, flat: jnp.ndarray, num: int, dispatch, resolutions
) -> jnp.ndarray:
    """Σ of ``values`` (E, *chunk) by flat segment ids into ``num`` rows
    through the ``segment_sum`` dispatch op: (num, *chunk)."""
    e, chunk = values.shape[0], values.shape[1:]
    d = math.prod(chunk)
    info = {"nnz": e, "dim": d, "num_segments": num, "dtype": values.dtype}
    impl = kernels.resolve_impl("segment_sum", info, dispatch)
    _note(resolutions, "segment_sum", f"E={e},D={d},S={num}", impl, info)
    if impl.tier == "jnp":
        return jax.ops.segment_sum(values, flat, num_segments=num)
    return impl.fn(values.reshape((e, d)), flat, num).reshape((num,) + chunk)


def block_rows(nnz: int, row_bytes: int) -> Tuple[int, int]:
    """``(rows per block, blocks)`` for ``nnz`` message rows of
    ``row_bytes`` each under ``JOIN_AGG_BLOCK_BYTES``: one block where
    they all fit, else blocks of a multiple of ``BLOCK_ROWS_QUANTUM``
    rows (the last one ragged)."""
    if nnz * row_bytes <= JOIN_AGG_BLOCK_BYTES:
        return nnz, 1
    rows = JOIN_AGG_BLOCK_BYTES // max(row_bytes, 1)
    rows = max(BLOCK_ROWS_QUANTUM, rows - rows % BLOCK_ROWS_QUANTUM)
    return rows, -(-nnz // rows)


def _row_blocks(arrays, rows: int, blocks: int, pad_values):
    """``arrays`` (each with nnz leading rows) padded to ``blocks × rows``
    rows with ``pad_values`` and split as (blocks, rows, ...)."""
    out = []
    for a, fill in zip(arrays, pad_values):
        extra = blocks * rows - a.shape[0]
        a = jnp.pad(a, ((0, extra),) + ((0, 0),) * (a.ndim - 1), constant_values=fill)
        out.append(a.reshape((blocks, rows) + a.shape[1:]))
    return out


def _coo_join_agg(
    join: fra.Join,
    grp: KeyFn,
    lrel: AnyRel,
    rrel: AnyRel,
    dispatch,
    resolutions,
    shards: Optional[NnzShards],
) -> Optional[DenseRelation]:
    """Σ_grp(COO ⋈ Dense) as one operation: gather each COO row's dense
    row, apply ⊗, and Σ the results by the output key, one block of nnz
    rows at a time (``block_rows``), so that no nnz × width array beyond
    one block exists. ``shards`` set (the COO's nnz rows on the data
    axes): each shard runs this on its own rows inside ``shard_map``, with
    the dense side whole, and ``exchange`` reduce-scatters the partial
    sums to the row owners. Returns None where the one-block lowering
    (``_coo_join`` then Σ) is the same operation: a call that fits one
    block on one device, or a shape this does not cover. Raises what
    ``_coo_join`` raises for a join it cannot lower."""
    coo, dense, coo_is_left, d2c = _coo_operands(join, lrel, rrel)
    if dense.key_arity == 0 or any(isinstance(c, Lit) for c in grp.comps):
        return None
    seg_cols, extents = [], []
    for c in grp.comps:
        pc = join.proj.comps[c.idx]
        if isinstance(pc, Lit):
            return None
        own = isinstance(pc, L) == coo_is_left
        seg_cols.append(pc.idx if own else d2c[pc.idx])
        extents.append(coo.extents[pc.idx] if own else dense.extents[pc.idx])
    gather_cols = [d2c[j] for j in range(dense.key_arity)]
    kfn = _vmapped(join.kernel.fn, 1)

    def apply(v, g):
        return kfn(v, g) if coo_is_left else kfn(g, v)

    out = jax.eval_shape(
        apply,
        jax.ShapeDtypeStruct((1,) + coo.chunk_shape, coo.values.dtype),
        jax.ShapeDtypeStruct((1,) + dense.chunk_shape, dense.data.dtype),
    )
    chunk, dtype = out.shape[1:], out.dtype
    n = shards.size if shards is not None else 1
    if coo.nnz == 0 or coo.nnz % n:
        return None
    e = coo.nnz // n
    d = math.prod(chunk)
    rows, blocks = block_rows(e, d * jnp.dtype(dtype).itemsize)
    num = math.prod(extents)
    # padded up so that the exchange hands each shard an equal run of rows
    num_pad = -(-num // n) * n if extents else 1
    inner = dispatch.per_shard() if shards is not None and dispatch is not None else dispatch
    g = kernels.resolve_impl("gather_join", {
        "rows": rows, "num_rows": math.prod(dense.extents),
        "dim": math.prod(dense.chunk_shape), "dtype": dense.data.dtype}, inner)
    s = kernels.resolve_impl("segment_sum", {
        "nnz": rows, "dim": d, "num_segments": num_pad, "dtype": dtype}, inner)
    tier = g.tier if g.tier == s.tier else f"{g.tier}+{s.tier}"
    _note(resolutions, "join_agg", f"E={e},D={d},S={num},blocks={blocks}", tier)
    if shards is None and blocks == 1:
        return None

    def block_sum(table, keys, values):
        idx = tuple(keys[:, c] for c in gather_cols)
        gathered = _dispatched_gather(table, idx, inner, resolutions)
        vals = _mask_padded_rows(keys, apply(values, gathered))
        flat = jnp.zeros((keys.shape[0],), jnp.int32)
        stride = 1
        for col, ext in zip(reversed(seg_cols), reversed(extents)):
            flat = flat + keys[:, col].astype(jnp.int32) * stride
            stride *= ext
        return _dispatched_segment_sum(vals, flat, num_pad, inner, resolutions)

    def local(keys, values, data):
        if blocks == 1:
            return block_sum(DenseRelation(data, dense.key_arity), keys, values)
        kb, vb = _row_blocks((keys, values), rows, blocks, (COO_PAD_KEY, 0))
        # the loop carries the table in the row gather's (rows, 1, width)
        # view: a TPU lays that out apart from (rows, width), and the one
        # copy between them is then made once, before the loop, and not
        # carried beside the table it copies
        rows3 = data.reshape(math.prod(data.shape[:dense.key_arity]), 1, -1)

        def body(i, acc):
            table = DenseRelation(rows3.reshape(data.shape), dense.key_arity)
            return acc + block_sum(table, kb[i], vb[i])

        return jax.lax.fori_loop(0, blocks, body, jnp.zeros((num_pad,) + chunk, dtype))

    # Each shard gathers from the whole table. A one-key table goes in
    # row-sharded, padded to whole tiles a shard, and is all-gathered
    # here: XLA's own all-gather of unevenly sharded rows (2,449,029 over
    # four chips) compiles for minutes on a TPU.
    gathered = shards is not None and dense.key_arity == 1
    table = dense.data
    if gathered:
        pad = -dense.extents[0] % (n * TABLE_ROWS_QUANTUM)
        table = jnp.pad(table, ((0, pad),) + ((0, 0),) * len(dense.chunk_shape))

    def per_shard(keys, values, data):
        if gathered:
            with jax.named_scope(EXCHANGE):
                data = jax.lax.all_gather(data, shards.axes, tiled=True)
        return exchange(local(keys, values, data), shards.axes, scatter=bool(extents))

    with jax.named_scope(JOIN_AGG):
        if shards is None:
            summed = local(coo.keys, coo.values, dense.data)
        else:
            summed = jax.shard_map(
                per_shard,
                mesh=shards.mesh,
                in_specs=(
                    shards.spec(2),
                    shards.spec(coo.values.ndim),
                    shards.spec(table.ndim) if gathered else P(),
                ),
                out_specs=shards.spec(1 + len(chunk)) if extents else P(),
                check_vma=False,
            )(coo.keys, coo.values, table)
    return DenseRelation(
        summed[:num].reshape(tuple(extents) + chunk), key_arity=len(extents)
    )


# ---------------------------------------------------------------------------
# Restrict lowering: fused per-tuple gather for sparse gradients
# ---------------------------------------------------------------------------


def _solve_side_from_output(
    pred: JoinPred, proj: JoinProj, la: int, ra: int
):
    """For Restrict(Join(...), coo): reconstruct each input key component of
    the join from the *output* key columns (+ pred equalities). Returns
    (left_exprs, right_exprs) where each expr is an output column index or
    a Lit, or None if some component is underdetermined."""
    uf = join_equiv_classes(pred, la, ra)
    col_of: Dict[object, object] = {}
    for p, c in enumerate(proj.comps):
        if isinstance(c, Lit):
            continue
        col_of.setdefault(uf.find(c), p)
    for a, b in pred.eqs:
        for c in (a, b):
            if isinstance(c, Lit):
                col_of.setdefault(uf.find(c), Lit(c.val))

    def solve(comps):
        out = []
        for c in comps:
            e = col_of.get(uf.find(c))
            if e is None:
                return None
            out.append(e)
        return out

    lex = solve([L(i) for i in range(la)])
    rex = solve([R(j) for j in range(ra)])
    if lex is None or rex is None:
        return None
    return lex, rex


def _restricted_join(
    join: fra.Join,
    ref: CooRelation,
    lrel: AnyRel,
    rrel: AnyRel,
    dispatch=None,
    resolutions: Optional[Dict] = None,
    shards: Optional[NnzShards] = None,
) -> CooRelation:
    """Evaluate a dense⋈dense join only at the key set of ``ref``: gather
    both operands per ref-tuple and apply the kernel pointwise. This is the
    sparse-gradient fast path (e.g. ∂loss/∂edge_weights = g[dst]·h[src]);
    the per-tuple gathers route through the ``gather_join`` dispatch op.
    Rows whose gathered operands exceed ``JOIN_AGG_BLOCK_BYTES`` run in
    blocks of ref rows, so only the ref-shaped output is ever whole; with
    ``shards`` (ref's nnz rows on the data axes) each shard takes its own
    rows inside ``shard_map`` against the whole dense operands."""
    if not (isinstance(lrel, DenseRelation) and isinstance(rrel, DenseRelation)):
        raise LoweringError("restricted join requires dense operands")
    la, ra = join.left.key_arity, join.right.key_arity
    solved = _solve_side_from_output(join.pred, join.proj, la, ra)
    if solved is None:
        raise LoweringError("restricted join underdetermined (needs Σ)")
    lex, rex = solved
    tgt = ref.chunk_shape
    kfn = _vmapped(join.kernel.fn, 1)
    if shards is not None and ref.nnz % shards.size:
        shards = None
    e = ref.nnz // (shards.size if shards is not None else 1)
    table = dispatch.per_shard() if shards is not None and dispatch is not None else dispatch

    def at_rows(keys, ldata, rdata):
        nnz = keys.shape[0]

        def gather(rel: DenseRelation, exprs):
            idx = []
            for e in exprs:
                if isinstance(e, Lit):
                    idx.append(jnp.full((nnz,), e.val, dtype=keys.dtype))
                else:
                    idx.append(keys[:, e])
            return (
                _dispatched_gather(rel, tuple(idx), table, resolutions)
                if idx
                else jnp.broadcast_to(rel.data, (nnz,) + rel.chunk_shape)
            )

        lv = gather(DenseRelation(ldata, lrel.key_arity), lex)
        rv = gather(DenseRelation(rdata, rrel.key_arity), rex)
        vals = _mask_padded_rows(keys, kfn(lv, rv))
        # Chunk-level broadcasting in the forward kernel (e.g. scalar edge
        # weight × embedding chunk) dualizes to a reduction in the backward:
        # sum the VJP chunk down to the target relation's chunk shape.
        extra = (vals.ndim - 1) - len(tgt)
        if extra > 0:
            vals = jnp.sum(vals, axis=tuple(range(1, 1 + extra)))
        for ax, (got, want) in enumerate(zip(vals.shape[1:], tgt)):
            if got != want:
                assert want == 1, (vals.shape, tgt)
                vals = jnp.sum(vals, axis=1 + ax, keepdims=True)
        return vals

    width = max(
        math.prod(lrel.chunk_shape) * lrel.data.dtype.itemsize,
        math.prod(rrel.chunk_shape) * rrel.data.dtype.itemsize,
    )
    rows, blocks = block_rows(e, width)

    def local(keys, ldata, rdata):
        if blocks == 1:
            return at_rows(keys, ldata, rdata)
        (kb,) = _row_blocks((keys,), rows, blocks, (COO_PAD_KEY,))
        out = jax.lax.map(lambda k: at_rows(k, ldata, rdata), kb)
        return out.reshape((blocks * rows,) + out.shape[2:])[:e]

    if shards is None:
        vals = local(ref.keys, lrel.data, rrel.data)
    else:
        vals = jax.shard_map(
            local,
            mesh=shards.mesh,
            in_specs=(shards.spec(2), P(), P()),
            out_specs=shards.spec(1 + len(tgt)),
            check_vma=False,
        )(ref.keys, lrel.data, rrel.data)
    return CooRelation(
        ref.keys, vals, ref.extents, ref.owner_dim, ref.shard_offsets
    )


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------


def _execute_graph(
    root: fra.Node,
    env: Env,
    cache: Optional[Env] = None,
    *,
    fuse_join_agg: bool = True,
    dispatch=None,
    resolutions: Optional[Dict] = None,
    nnz_shards: Optional[NnzShards] = None,
) -> AnyRel:
    """Walk a query graph over chunked relations, lowering each node to XLA
    ops. This is the engine's *lowering primitive*: it runs once per trace
    on the staged path (core/engine.py) and once per call on the eager
    path.

    ``fuse_join_agg=False`` materializes every Join's output individually
    instead of fusing Σ∘⋈ into one einsum — needed when a gradient program
    built *without* the §4 join-agg-fusion optimization will consume the
    join intermediates (benchmarks/rjp_ablation.py).

    ``dispatch`` is a kernels.DispatchTable (None → backend default)
    steering the segment-sum / blocked-matmul hot-spots to a physical
    tier; ``resolutions`` (optional dict) collects ``op[site] → tier``
    records of every dispatch decision made during the walk.
    ``nnz_shards`` names the env's COO relations whose nnz rows the plan
    puts on a mesh's data axes: their join-aggregates and restricted
    joins run per shard (``_coo_join_agg``)."""
    memo: Dict[int, AnyRel] = {}
    sharded = (
        {id(env[k]) for k in nnz_shards.names if k in env}
        if nnz_shards is not None
        else set()
    )

    def shards_of(rel) -> Optional[NnzShards]:
        return nnz_shards if id(rel) in sharded else None

    def ex(n: fra.Node) -> AnyRel:
        if n.id in memo:
            return memo[n.id]
        out = _ex(n)
        memo[n.id] = out
        if cache is not None:
            cache[f"__fwd_{n.id}"] = out
        return out

    def _join(n: fra.Join, grp: Optional[KeyFn]) -> AnyRel:
        lrel, rrel = ex(n.left), ex(n.right)
        if isinstance(lrel, CooRelation) or isinstance(rrel, CooRelation):
            if isinstance(lrel, CooRelation) and isinstance(rrel, CooRelation):
                raise LoweringError("COO ⋈ COO not supported")
            if grp is not None:
                coo = lrel if isinstance(lrel, CooRelation) else rrel
                fused = _coo_join_agg(
                    n, grp, lrel, rrel, dispatch, resolutions, shards_of(coo)
                )
                if fused is not None:
                    return fused
            out = _coo_join(n, lrel, rrel, dispatch, resolutions)
            if grp is not None:
                out = _agg_coo(grp, out)
            return out
        # dense ⋈ dense
        k = n.kernel
        if k.elementwise or k.chunk_spec is not None:
            try:
                return _einsum_join(
                    n, grp, lrel, rrel, dispatch=dispatch, resolutions=resolutions
                )
            except LoweringError:
                pass
        al = _aligned_join(n, lrel, rrel)
        if al is not None:
            if grp is not None:
                al = _agg_dense(grp, al)
            return al
        bc = _broadcast_join(n, grp, lrel, rrel)
        if bc is not None:
            return bc
        raise LoweringError(f"cannot lower join {n.describe()}")

    def _agg_dense(grp: KeyFn, rel: DenseRelation) -> DenseRelation:
        arity = rel.key_arity
        if all(isinstance(c, Lit) for c in grp.comps) and grp.arity_out == 0:
            data = jnp.sum(
                rel.data, axis=tuple(range(arity))
            ) if arity else rel.data
            return DenseRelation(data, key_arity=0)
        if any(isinstance(c, Lit) for c in grp.comps):
            raise LoweringError("mixed Lit grp over dense not supported")
        keep = [c.idx for c in grp.comps]
        if len(set(keep)) != len(keep):
            raise LoweringError("duplicate grp components over dense")
        drop = tuple(i for i in range(arity) if i not in keep)
        data = jnp.sum(rel.data, axis=drop) if drop else rel.data
        # axes now ordered by ascending original idx; permute to grp order
        remaining = [i for i in range(arity) if i not in drop]
        perm = [remaining.index(i) for i in keep]
        data = jnp.transpose(
            data, tuple(perm) + tuple(range(len(keep), data.ndim))
        )
        return DenseRelation(data, key_arity=len(keep))

    def _agg_coo(grp: KeyFn, rel: CooRelation) -> DenseRelation:
        if any(isinstance(c, Lit) for c in grp.comps):
            raise LoweringError("Lit grp over COO not supported")
        keep = [c.idx for c in grp.comps]
        extents = tuple(rel.extents[i] for i in keep)
        if rel.nnz == 0:
            # zero-nnz guard: the registered tiers can disagree on the
            # dtype/shape of a segment_sum over empty arrays — the Σ of
            # no tuples is the ⊕-unit grid, emitted without dispatching
            return DenseRelation(
                jnp.zeros(extents + rel.chunk_shape, dtype=rel.values.dtype),
                key_arity=len(extents),
            )
        if not extents:
            return DenseRelation(jnp.sum(rel.values, axis=0), key_arity=0)
        flat = jnp.zeros((rel.nnz,), dtype=jnp.int32)
        stride = 1
        for i in reversed(range(len(keep))):
            flat = flat + rel.keys[:, keep[i]].astype(jnp.int32) * stride
            stride *= extents[i]
        num = math.prod(extents)
        summed = _dispatched_segment_sum(
            rel.values, flat, num, dispatch, resolutions
        )
        return DenseRelation(
            summed.reshape(extents + rel.chunk_shape), key_arity=len(extents)
        )

    def _ex(n: fra.Node) -> AnyRel:
        if isinstance(n, fra.TableScan):
            return env[n.name]
        if isinstance(n, fra.Const):
            return env[n.ref]
        if isinstance(n, fra.Select):
            rel = ex(n.child)
            if isinstance(rel, CooRelation):
                if not n.pred.always_true:
                    raise LoweringError("predicated σ over COO not supported")
                cols = []
                extents = []
                for c in n.proj.comps:
                    if isinstance(c, Lit):
                        raise LoweringError("Lit proj over COO")
                    cols.append(rel.keys[:, c.idx])
                    extents.append(rel.extents[c.idx])
                keys = jnp.stack(cols, axis=1)
                vals = _vmapped(n.kernel.fn, 1)(rel.values)
                # σ kernels with f(0) != 0 would resurrect padded rows;
                # re-mask so they stay inert through full-reduce Σs
                vals = _mask_padded_rows(rel.keys, vals)
                return CooRelation(keys, vals, tuple(extents))
            if n.pred.custom is not None:
                raise LoweringError("custom σ predicate not compilable")
            fixed = dict(n.pred.eqs)
            data = rel.data
            # slice fixed components (descending so axes stay valid)
            for i in sorted(fixed, reverse=True):
                data = jnp.take(data, fixed[i], axis=i)
            remaining = [i for i in range(n.child.key_arity) if i not in fixed]
            proj_idx = []
            for c in n.proj.comps:
                if isinstance(c, Lit):
                    raise LoweringError("Lit σ projection over dense")
                if c.idx in fixed:
                    raise LoweringError("σ projects a predicate-fixed component")
                proj_idx.append(remaining.index(c.idx))
            if sorted(proj_idx) != list(range(len(remaining))):
                raise LoweringError("σ projection must permute surviving comps")
            chunk_axes = tuple(range(len(remaining), data.ndim))
            data = jnp.transpose(data, tuple(proj_idx) + chunk_axes)
            data = _vmapped(n.kernel.fn, len(proj_idx))(data)
            return DenseRelation(data, key_arity=len(proj_idx))
        if isinstance(n, fra.Agg):
            if isinstance(n.child, fra.Join) and fuse_join_agg:
                if not n.kernel.is_add:
                    raise LoweringError("non-additive Σ over ⋈ not supported")
                return _join(n.child, n.grp)
            rel = ex(n.child)
            if not n.kernel.is_add:
                raise LoweringError("non-additive Σ not supported in compiler")
            if isinstance(rel, CooRelation):
                return _agg_coo(n.grp, rel)
            return _agg_dense(n.grp, rel)
        if isinstance(n, fra.Join):
            return _join(n, None)
        if isinstance(n, fra.Restrict):
            ref = ex(n.ref)
            if isinstance(ref, DenseRelation):
                # Full-grid key set: the restriction is the identity.
                return ex(n.child)
            assert isinstance(ref, CooRelation)
            if isinstance(n.child, fra.Join):
                lrel, rrel = ex(n.child.left), ex(n.child.right)
                if isinstance(lrel, DenseRelation) and isinstance(rrel, DenseRelation):
                    return _restricted_join(
                        n.child, ref, lrel, rrel, dispatch, resolutions,
                        shards_of(ref),
                    )
            child = ex(n.child)
            if isinstance(child, CooRelation):
                # By construction RJP outputs over a sparse target reuse the
                # target's key order.
                return child
            # Dense child: gather at ref keys (padding rows gather zeros).
            idx = tuple(ref.keys[:, i] for i in range(ref.key_arity))
            vals = _dispatched_gather(child, idx, dispatch, resolutions)
            return CooRelation(
                ref.keys, vals, ref.extents, ref.owner_dim, ref.shard_offsets
            )
        if isinstance(n, fra.AddOp):
            a, b = ex(n.left), ex(n.right)
            if isinstance(a, DenseRelation) and isinstance(b, DenseRelation):
                return DenseRelation(a.data + b.data, a.key_arity)
            if isinstance(a, DenseRelation) and isinstance(b, CooRelation):
                a, b = b, a
            if isinstance(a, CooRelation) and isinstance(b, DenseRelation):
                idx = tuple(a.keys[:, i] for i in range(a.key_arity))
                return DenseRelation(b.data.at[idx].add(a.values), b.key_arity)
            raise LoweringError("COO + COO add not supported")
        raise TypeError(f"unknown node {n}")

    return ex(root)


def execute(
    root: fra.Node,
    env: Env,
    cache: Optional[Env] = None,
    *,
    fuse_join_agg: bool = True,
    dispatch=None,
) -> AnyRel:
    """Eager execution: the engine's eager mode on an anonymous graph —
    re-walks the graph on every call, no engine registered (callers often
    build throwaway graphs; interning them would only pin memory). Use
    the ``repro.Database`` session (``db.query(...)`` /
    ``db.execute(...)``) for the cached jit path.

    ``dispatch`` accepts anything ``kernels.make_table`` does (a tier
    name, a {op: tier} dict, a DispatchTable); None keeps the backend
    default (jnp lowerings on CPU, Pallas kernels on TPU)."""
    table = kernels.make_table(dispatch)
    return _execute_graph(
        root, env, cache, fuse_join_agg=fuse_join_agg, dispatch=table
    )


def run_query(q: fra.Query, env: Env, *, dispatch=None) -> AnyRel:
    """Eager execution of a whole Query (see ``execute``)."""
    table = kernels.make_table(dispatch)
    return _execute_graph(q.root, env, dispatch=table)


def execute_with_cache(
    root: fra.Node, env: Env, *, fuse_join_agg: bool = True, dispatch=None
) -> Tuple[AnyRel, Env]:
    """Forward pass caching every evaluated node's chunked relation, for the
    compiled gradient path (Algorithm 2 line 6). Joins consumed by a fusing
    Agg are evaluated as part of the fused einsum and are not individually
    cached — the §4-optimized RJPs never consume them, only their children
    (which are cached). Pass ``fuse_join_agg=False`` when the gradient
    program was built without join-agg fusion and needs the join
    intermediates."""
    fwd: Env = {}
    table = kernels.make_table(dispatch)
    out = _execute_graph(
        root, env, cache=fwd, fuse_join_agg=fuse_join_agg, dispatch=table
    )
    return out, fwd


def grad_eval(
    prog,
    env: Env,
    seed: Optional[AnyRel] = None,
    *,
    fuse_join_agg: bool = True,
    dispatch=None,
) -> Tuple[AnyRel, Dict[str, AnyRel]]:
    """Execute a GradientProgram (autodiff.py) on the compiled path:
    chunked forward with cache, then each gradient query graph. Thin
    wrapper over the engine's eager mode; the staged equivalent is a
    ``repro.Database`` handle's ``step()``. ``dispatch`` steers the
    kernel tier of both the forward and every gradient graph, so the
    gradient queries differentiate *through* whatever physical forward
    (Pallas included) the table selects."""
    from .engine import engine_for

    return engine_for(prog, fuse_join_agg=fuse_join_agg).eager(
        env, seed, dispatch=dispatch
    )
