"""Staged query engine: lower → plan → jit-compile.

The paper's systems claim (§1) is that a relational engine *automatically
distributes* differentiated queries: the optimizer picks a physical plan
per join, the execution engine inserts the implied collectives, and the
whole thing is compiled once and reused across training iterations. This
module is that pipeline, staged explicitly in the jax.stages idiom
(wrapped → lowered → compiled):

    RAEngine(program)             # FRA query / gradient program (wrapped)
        .lower(env)               # → Lowered: abstract-shape trace of the
                                  #   chunked lowering, cached per
                                  #   (graph, shapes/dtypes, dispatch
                                  #   table) signature
        .compile(mesh=...)        # → Compiled: planner.plan_query picks a
                                  #   JoinPlan per join — 2-D (data ×
                                  #   model) on a launch/mesh mesh — its
                                  #   PartitionSpecs become jax.jit
                                  #   in_shardings, XLA SPMD inserts the
                                  #   plan's collectives
    compiled(env)                 # jit-cached step: zero re-lowering

Kernel dispatch is part of the lowering: ``lower(env, dispatch=...)``
pins a kernels.DispatchTable (Pallas / interpret / ref / jnp tier per hot
op) into the lowering signature, so switching tiers re-lowers and jits a
distinct step — kernel choice can never alias a stale jit cache entry.
The decisions actually taken are recorded on ``Compiled.resolutions``.

``RAEngine.trace_count`` counts actual FRA-graph walks (lowerings). A
``Compiled`` step re-walks the graph only when jit retraces — i.e. never,
for a fixed environment signature; the engine-stage tests assert this.

Relations cross the jit boundary as pytrees (relation.py registers
``DenseRelation``/``CooRelation`` with key arity / extents as static aux
data), so a whole relation environment is one argument and every
relation's block axes can carry a planner-emitted sharding.

Eager mode (``RAEngine.eager`` / ``compiler.execute``) walks the graph on
every call — it is the un-staged path kept for debugging and for the
oracle cross-checks.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import warnings
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from . import fra, kernels, planner
from . import rewrite as _rewrite
from .autodiff import GradientProgram
from .relation import CooRelation, DenseRelation, pad_coo_nnz
from .spans import (
    ENGINE_CALL,
    ENGINE_DISPATCH,
    ENGINE_LOWER,
    ENGINE_PLAN,
    span,
    spanned,
)

AnyRel = Union[DenseRelation, CooRelation]
Env = Dict[str, AnyRel]
Program = Union[fra.Query, fra.Node, GradientProgram]

#: per-Lowered bound on retained Compiled executables (LRU): generous for
#: real mesh/donate/stats-bucket churn, small enough that key-churning
#: callers cannot accrete XLA executables without bound.
_MAX_COMPILED = 64


class ShardFallbackWarning(UserWarning):
    """A planned sharding could not be emitted and the relation fell back
    to replication. Structured: carries the relation name, the offending
    dim/extent, and the divisor, so callers can grep/assert on them."""

    def __init__(self, relation: str, dim: int, extent: int, divisor: int):
        self.relation = relation
        self.dim = dim
        self.extent = extent
        self.divisor = divisor
        super().__init__(
            f"relation {relation!r}: planned sharding of block dim {dim} "
            f"(extent {extent}) dropped — not divisible by the mesh axes' "
            f"product {divisor}; the dim is replicated instead"
        )


class ReshardWarning(UserWarning):
    """``Compiled.__call__`` moved committed input bytes to the planned
    layout via device_put — an all-to-all the plan did not account for.
    Structured (carries the relation name and the bytes moved) and
    emitted once per *(cache entry, relation)*, so a second offending
    relation is reported too instead of being swallowed by the first.
    See ``Compiled.counters["reshard"]``; fold the cost into planning with
    ``compile(committed=...)`` or let ``compile_auto`` / the ``Database``
    session thread it automatically."""

    def __init__(self, relation: str, bytes_moved: int):
        self.relation = relation
        self.bytes_moved = bytes_moved
        super().__init__(
            f"relation {relation!r}: Compiled step resharded {bytes_moved} "
            f"committed input bytes to the planned layout (an all-to-all "
            f"the plan did not cost); pass committed= layouts to compile() "
            f"— or step through repro.Database, which auto-threads them — "
            f"to fold it into the plan. See Compiled.counters['reshard']."
        )


# ---------------------------------------------------------------------------
# Environment signatures: the lowering-cache key
# ---------------------------------------------------------------------------


def _rel_signature(name: str, rel: AnyRel) -> Tuple:
    if isinstance(rel, DenseRelation):
        return (
            name,
            "dense",
            rel.key_arity,
            tuple(rel.data.shape),
            str(rel.data.dtype),
        )
    if isinstance(rel, CooRelation):
        return (
            name,
            "coo",
            tuple(rel.extents),
            tuple(rel.keys.shape),
            str(rel.keys.dtype),
            tuple(rel.values.shape),
            str(rel.values.dtype),
            rel.owner_dim,
            rel.shard_offsets,
        )
    raise TypeError(f"env entry {name!r} is not a relation: {type(rel)}")


def env_signature(env: Env, seed: Optional[AnyRel] = None) -> Tuple:
    """Hashable (graph-independent) structure+shape+dtype key for an
    environment — the lowering cache is keyed on this per engine."""
    sig = tuple(_rel_signature(n, env[n]) for n in sorted(env))
    if seed is not None:
        sig += (_rel_signature("__seed_arg", seed),)
    return sig


def _stats_key(stats) -> Optional[Tuple]:
    """Hashable snapshot key for a {name: RelationStats} dict (the stats
    part of a Compiled cache key). Counts are quantized to powers of two
    (``RelationStats.quantized``): statistics jitter across refreshes of
    the same-shaped relation lands on the same key — and therefore the
    same cached plan — while an order-of-magnitude shift re-plans."""
    if not stats:
        return None
    return tuple(sorted((n, st.quantized()) for n, st in stats.items()))


def _norm_spec(spec) -> Tuple:
    """PartitionSpec normalized for layout comparison: trailing
    replicated dims dropped, so ``P('data')`` and ``P('data', None)``
    describe the same placement."""
    t = tuple(spec) if spec is not None else ()
    while t and t[-1] is None:
        t = t[:-1]
    return t


def _abstract(rel):
    """Replace array leaves with ShapeDtypeStructs (relation containers and
    their static aux data survive — relations are pytrees)."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype), rel
    )


# ---------------------------------------------------------------------------
# Compiled: the jitted executable with planner-emitted shardings
# ---------------------------------------------------------------------------


class Compiled:
    """A jit-compiled, plan-annotated executable for one environment
    signature. Calling it with a same-signature environment hits the jit
    cache: the FRA graph is never re-walked.

    Cache-key semantics: a Compiled is cached on its parent ``Lowered``
    under ``(mesh, axis, donate, mem_budget, n_devices, geometry)`` where
    ``geometry`` is the planner's ``MeshGeometry`` read off the mesh; the
    Lowered itself is cached on the engine under ``(env signature,
    dispatch table)``. Everything that changes the traced computation —
    shapes, dtypes, relation layouts, kernel tiers, mesh shape — is
    therefore part of some cache key, and a Compiled can only ever be
    replayed on environments whose signature matches the one it was
    lowered for (``__call__`` re-checks and raises otherwise)."""

    def __init__(
        self,
        lowered: "Lowered",
        jitted,
        donate_names: Tuple[str, ...],
        plans: Dict[int, planner.JoinPlan],
        input_specs: Dict[str, P],
        mesh,
        geometry: Optional[planner.MeshGeometry] = None,
        in_shardings: Optional[Tuple[Dict, Dict]] = None,
        pad_nnz: Optional[Dict[str, int]] = None,
        rechunks: Optional[Dict[str, int]] = None,
    ):
        self.lowered = lowered
        self._jitted = jitted
        self.donate_names = donate_names
        #: planner.JoinPlan per Join node id — the chosen physical plans.
        self.plans = plans
        #: planner-emitted PartitionSpec per base relation (pre-padding).
        self.input_specs = input_specs
        self.mesh = mesh
        #: the (data × model) MeshGeometry this executable was planned for.
        self.geometry = geometry
        #: (donated, kept) relation-shaped sharding pytrees when a mesh
        #: was given; __call__ reshards inputs to the planned layout.
        self.in_shardings = in_shardings
        #: COO relations whose nnz axis is padded to a shard multiple
        #: (pad-and-mask): relation name → padded row count. __call__ pads
        #: inputs and slices nnz-shaped outputs back.
        self.pad_nnz = dict(pad_nnz or {})
        #: the planner's *rechunk stage*: relations whose committed layout
        #: differed from the plan's chosen grid at compile time, so the
        #: re-blocking all-to-all was costed into the plan (name → bytes).
        #: __call__ counts these moves under ``planned_bytes`` and does
        #: not warn — only unplanned moves are "silent" reshards.
        self.rechunks: Dict[str, int] = dict(rechunks or {})
        #: device-layout rechunk accounting for the silent-reshard path:
        #: calls, calls that moved committed bytes, cumulative and
        #: last-call bytes moved by __call__'s device_put; plus the
        #: cumulative bytes of plan-aware (costed, warning-free) rechunks.
        #: Read it as ``Compiled.counters["reshard"]`` (or aggregated over
        #: a whole session as ``db.counters()["reshard"]``).
        self._reshard: Dict[str, int] = {
            "calls": 0,
            "resharded_calls": 0,
            "bytes_moved": 0,
            "last_call_bytes": 0,
            "planned_bytes": 0,
        }
        #: relations already warned about — ReshardWarning fires once per
        #: (cache entry, relation), not once per cache entry.
        self._reshard_warned: set = set()
        # flattened target leaves per relation, precomputed so the per-call
        # accounting never re-walks the sharding pytrees
        self._reshard_targets = (
            {
                name: jax.tree_util.tree_leaves(target)
                for shards in in_shardings
                for name, target in shards.items()
            }
            if in_shardings is not None
            else {}
        )

    @property
    def dispatch(self) -> kernels.DispatchTable:
        """The kernel DispatchTable this executable was lowered under."""
        return self.lowered.dispatch

    @property
    def counters(self) -> Dict[str, Dict[str, int]]:
        """This executable's slice of the unified telemetry tree —
        currently ``{"reshard": {...}}`` (calls / resharded_calls /
        bytes_moved / last_call_bytes / planned_bytes, all live dicts).
        Sessions aggregate the same keys over every executable they
        compiled as ``db.counters()["reshard"]``."""
        return {"reshard": self._reshard}

    @property
    def resolutions(self) -> Dict[str, str]:
        """``op[site] → tier`` record of every kernel-dispatch decision
        taken while lowering (e.g. ``segment_sum[E=320000,D=32,S=20000]``
        → ``'pallas'``)."""
        return dict(self.lowered.resolutions)

    @property
    def placements(self) -> Dict[str, Dict[str, Optional[int]]]:
        """``relation → {"data": dim, "model": dim}`` record of the 2-D
        placement of every base relation: which axis carries the mesh's
        (folded) data axes and which carries the model axis (``None`` =
        replicated on that mesh axis). For a CooRelation, dim 0 is the
        physical nnz row axis — ``{"data": 0}`` is the nnz-sharded
        layout. The distribution analogue of ``resolutions``. Compiled
        against a mesh, this reads the *effective* in_shardings (after
        non-divisible dense axes were dropped and non-divisible nnz axes
        padded); without a mesh it reports the planner's intent from
        ``input_specs``."""
        geo = self.geometry
        model_axis = geo.model_axis if geo is not None else "model"
        data_axes = set(geo.data_axes) if geo is not None else set()

        def dims_of(spec) -> Dict[str, Optional[int]]:
            data_dim = model_dim = None
            for d, entry in enumerate(tuple(spec)):
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                if any(a in data_axes for a in axes):
                    data_dim = d
                if model_axis in axes:
                    model_dim = d
            return {"data": data_dim, "model": model_dim}

        if self.in_shardings is None:
            return {n: dims_of(s) for n, s in self.input_specs.items()}
        out: Dict[str, Dict[str, Optional[int]]] = {}
        for shards in self.in_shardings:
            for name, rel in shards.items():
                if isinstance(rel, DenseRelation):
                    out[name] = dims_of(rel.data.spec)
                else:  # CooRelation: values sharding covers (nnz, *chunk)
                    out[name] = dims_of(rel.values.spec)
        return out

    def planned_spec(self, name: str) -> Optional[P]:
        """The PartitionSpec this executable places relation ``name``'s
        payload array at (a DenseRelation's ``data`` / a CooRelation's
        ``values``) — the layout a ``_committed_layouts``-style probe of
        this step's *inputs after placement* would report. ``compile_auto``
        compares it against an env's committed layouts to decide whether a
        recorded plan still applies without any rechunk."""
        if self.in_shardings is None:
            return self.input_specs.get(name)
        for shards in self.in_shardings:
            rel = shards.get(name)
            if rel is not None:
                sh = rel.data if isinstance(rel, DenseRelation) else rel.values
                return sh.spec
        return None

    def _count_reshard_bytes(self, env: Env) -> Dict[str, int]:
        """Per-relation bytes of *committed* input arrays whose layout
        differs from the planned in_sharding — the silent all-to-all
        device_put pays. Uncommitted arrays place for free and cost only
        an attribute probe; the target leaves are precomputed at compile
        time."""
        moved: Dict[str, int] = {}
        for name, targets in self._reshard_targets.items():
            rel = env.get(name)
            if rel is None:
                continue
            for arr, sh in zip(jax.tree_util.tree_leaves(rel), targets):
                if not getattr(arr, "committed", False):
                    continue  # uncommitted inputs place for free
                cur = getattr(arr, "sharding", None)
                if getattr(cur, "is_fully_replicated", False):
                    continue  # slicing a replicated array moves nothing
                try:
                    same = cur is not None and cur.is_equivalent_to(sh, arr.ndim)
                except Exception:
                    same = cur == sh
                if not same:
                    moved[name] = moved.get(name, 0) + int(arr.nbytes)
        return moved

    def _padded(self, env: Env) -> Env:
        if not self.pad_nnz:
            return env
        out = dict(env)
        for name, target in self.pad_nnz.items():
            if name in out:
                out[name] = pad_coo_nnz(out[name], target)
        return out

    def _unpad(self, out):
        """Slice padded nnz axes out of the results: any output leaf whose
        leading dim exceeds the unpadded lowering's expectation (all other
        dims equal) is a row-aligned COO payload and is cut back."""
        def cut(got, want):
            wshape = tuple(want.shape)
            if (
                hasattr(got, "shape")
                and tuple(got.shape) != wshape
                and len(got.shape) == len(wshape)
                and wshape
                and got.shape[0] > wshape[0]
                and tuple(got.shape[1:]) == wshape[1:]
            ):
                return got[: wshape[0]]
            return got

        return jax.tree_util.tree_map(cut, out, self.lowered.out_shape)

    @spanned(ENGINE_CALL)
    def __call__(self, env: Env, seed: Optional[AnyRel] = None):
        sig = env_signature(env, seed)
        if sig != self.lowered.sig:
            raise ValueError(
                "environment signature does not match this Compiled's "
                "lowering; call RAEngine.lower(env) again for the new "
                f"shapes.\n  lowered: {self.lowered.sig}\n  got:     {sig}"
            )
        if self.in_shardings is not None:
            # Reshard accounting runs on the *pre-pad* env: padding makes
            # fresh (uncommitted) arrays, which would hide a committed
            # input's layout mismatch from the stats.
            moved_by_rel = self._count_reshard_bytes(env)
            # split plan-aware rechunks (costed at plan time, no warning)
            # from silent reshards the planner did not anticipate
            planned_by_rel = {
                n: b for n, b in moved_by_rel.items() if n in self.rechunks
            }
            moved_by_rel = {
                n: b for n, b in moved_by_rel.items() if n not in self.rechunks
            }
            moved = sum(moved_by_rel.values())
        env = self._padded(env)
        donated = {k: env[k] for k in self.donate_names}
        kept = {k: v for k, v in env.items() if k not in self.donate_names}
        if self.in_shardings is not None:
            # Reshard to the planned layout: inputs produced by an earlier
            # step may be committed to a different placement (e.g. a
            # gradient seed laid out by the forward's compiled output);
            # device_put inserts the re-blocking collective and is a
            # no-op when the layout already matches. The bytes moved are
            # counted on counters["reshard"] and warned about once — fold them
            # into the plan via compile(committed=...).
            sh_don, sh_kept = self.in_shardings
            stats = self._reshard
            stats["calls"] += 1
            stats["last_call_bytes"] = moved
            stats["planned_bytes"] += sum(planned_by_rel.values())
            if moved:
                stats["resharded_calls"] += 1
                stats["bytes_moved"] += moved
                for name, nbytes in moved_by_rel.items():
                    if name in self._reshard_warned:
                        continue  # already reported for this cache entry
                    self._reshard_warned.add(name)
                    warnings.warn(ReshardWarning(name, nbytes), stacklevel=2)
            donated = jax.device_put(donated, sh_don)
            kept = jax.device_put(kept, sh_kept)
        with span(ENGINE_DISPATCH):
            out = self._jitted(donated, kept, seed)
        return self._unpad(out) if self.pad_nnz else out

    def lower_text(self, *, compiled: bool = True) -> str:
        """HLO of the jitted step (diagnostics). ``compiled=True`` returns
        post-SPMD-partitioning HLO — the text in which the plan's
        collectives (all-reduce/all-gather) are visible; ``compiled=False``
        returns the pre-partitioning StableHLO."""
        abstract = dict(self.lowered.abstract_env)
        for name, target in self.pad_nnz.items():
            rel = abstract[name]
            abstract[name] = CooRelation(
                jax.ShapeDtypeStruct(
                    (target,) + tuple(rel.keys.shape[1:]), rel.keys.dtype
                ),
                jax.ShapeDtypeStruct(
                    (target,) + tuple(rel.values.shape[1:]), rel.values.dtype
                ),
                rel.extents,
                rel.owner_dim,
                rel.shard_offsets,
            )
        don = {k: abstract[k] for k in self.donate_names}
        kept = {
            k: v for k, v in abstract.items() if k not in self.donate_names
        }
        lowered = self._jitted.lower(don, kept, self.lowered.abstract_seed)
        if compiled:
            return lowered.compile().as_text()
        return lowered.as_text()


# ---------------------------------------------------------------------------
# Lowered: the shape-specialized lowering, pre-plan
# ---------------------------------------------------------------------------


class Lowered:
    """Abstract-shape lowering of an engine's program for one environment
    signature and one kernel DispatchTable. ``compile`` attaches a
    physical plan + jit.

    Cache-key semantics: the engine caches Lowereds under ``(sig,
    dispatch, rewrite-key)`` where ``sig`` is ``env_signature(env, seed)``
    — relation structure, key arities, shapes, dtypes — ``dispatch`` is
    the (hashable) DispatchTable, and the rewrite key is the enabled
    ``rewrite.RuleSet`` plus the quantized statistics snapshot the cost
    gate read (None when the rewrite stage is off). Two environments with
    equal signatures share a Lowered; a different tier table — or a
    statistics shift large enough to cross a quantization bucket and flip
    a gate — never does, so rewrite decisions are bit-stable like
    committed layouts."""

    def __init__(
        self,
        engine: "RAEngine",
        sig: Tuple,
        dispatch: kernels.DispatchTable,
        abstract_env: Env,
        abstract_seed,
        out_shape,
        resolutions: Dict[str, str],
        program: Optional[Program] = None,
        rewrite_report: Optional[_rewrite.RewriteReport] = None,
        check_report=None,
    ):
        self.engine = engine
        self.sig = sig
        #: validate-stage report (analysis.typecheck.CheckReport): the
        #: typed checker's diagnostics for the forward graph at this
        #: signature — error-free by construction (errors raise before a
        #: Lowered is built), warnings retained for db.check/explain.
        self.check_report = check_report
        #: the kernel tier table this lowering resolved against.
        self.dispatch = dispatch
        #: the program this lowering executes: the engine's program as
        #: rewritten by the cost-gated rewrite stage (core/rewrite.py),
        #: or the engine's own program when the stage was off/declined.
        self.program: Program = engine.program if program is None else program
        #: gate decisions of the rewrite stage (None when it was off).
        self.rewrite_report = rewrite_report
        self.abstract_env = abstract_env
        self.abstract_seed = abstract_seed
        #: pytree of ShapeDtypeStruct-leaved relations: the program output.
        self.out_shape = out_shape
        #: op[site] → tier decisions recorded during the lowering walk
        #: (a kernels.ResolutionLog: the dict plus per-site SiteRecords).
        self.resolutions = resolutions
        #: analysis.kernelcheck.certify_kernels caches its CheckReport
        #: here — the Lowered is already cached per (sig, dispatch,
        #: rewrite) key, so kernel certification is computed at most once
        #: per lowering and never on the execution hot path.
        self._kernel_report = None
        #: LRU-bounded: a Compiled holds an XLA executable, and callers
        #: that churn cache keys (committed layouts, stats buckets) must
        #: not accrete executables forever. Evicted entries simply
        #: recompile on next use; callers keep their own references.
        self._compiled: "OrderedDict[Tuple, Compiled]" = OrderedDict()
        #: compile_auto's plan record: per (mesh, donate, …) base key the
        #: Compiled whose committed-layout plan the catalog stands by.
        self._auto: "OrderedDict[Tuple, Compiled]" = OrderedDict()
        #: this lowering re-resolved under the tables ``compile`` derives
        #: for multi-device meshes (``kernels.table_for_mesh``).
        self._retabled: Dict[kernels.DispatchTable, "Lowered"] = {}

    def eager(self, env: Env, seed: Optional[AnyRel] = None):
        """Un-jitted execution (re-walks the graph; debugging only)."""
        return self.engine._execute(
            env, seed, dispatch=self.dispatch, program=self.program
        )

    def compile(
        self,
        mesh=None,
        *,
        axis: Optional[str] = None,
        donate: Tuple[str, ...] = (),
        mem_budget: float = planner.DEFAULT_MEM_BUDGET,
        n_devices: Optional[int] = None,
        committed: Optional[Dict[str, P]] = None,
        stats: Optional[Dict[str, planner.RelationStats]] = None,
    ) -> Compiled:
        """plan_query → in_shardings → jax.jit.

        ``mesh``: a jax Mesh — ``launch/mesh.make_host_mesh`` and
        ``make_production_mesh`` are the canonical constructors. The
        planner reads the real (data × model) geometry off it
        (``planner.MeshGeometry.from_mesh``): a 1-axis mesh reproduces
        the historical 1-D model-axis plans, a 2-D mesh adds per-relation
        batch-dim sharding over the (folded) data axes and may shard a
        CooRelation's nnz rows over them (padding non-divisible row
        counts — pad-and-mask — instead of falling back to replication).
        None compiles for the default (single-device) placement but still
        runs the planner (the plans are inspectable either way).
        ``axis`` overrides the name of the model axis (default: the
        mesh's ``"model"`` axis, or its sole axis).
        ``donate`` names env entries whose buffers jit may reuse
        (parameters / optimizer state on the training hot path). Note:
        a donated COO relation whose nnz is padded per call donates the
        padded *copy*, not the caller's buffer — pre-pad to the shard
        multiple (``relation.owner_partition`` / ``pad_coo_nnz``) so
        ``pad_nnz`` stays empty and donation reaches the real buffers.
        ``committed`` maps relation names to the PartitionSpec their
        arrays are already committed to (``_committed_layouts(env)``
        derives it): the planner then charges candidates that would force
        a device-layout rechunk, instead of ``Compiled.__call__`` paying
        the all-to-all silently (it still counts such moves on
        ``Compiled.counters["reshard"]``).
        ``stats`` maps relation names to tracked ``planner.RelationStats``
        (a ``Database`` catalog snapshot): the planner then replaces its
        Agg-size / edge-cut heuristics with measured key-domain
        statistics. The snapshot is part of the compile cache key —
        refreshed statistics re-plan, identical ones hit the cache.
        On a mesh of more than one device the step runs under
        ``kernels.table_for_mesh(self.dispatch, mesh)`` (no ``pallas``
        tier), and the returned Compiled's ``dispatch``/``resolutions``
        say so.
        """
        table = kernels.table_for_mesh(self.dispatch, mesh)
        if table != self.dispatch:
            return self._relowered(table).compile(
                mesh,
                axis=axis,
                donate=donate,
                mem_budget=mem_budget,
                n_devices=n_devices,
                committed=committed,
                stats=stats,
            )
        donate = tuple(sorted(donate))
        geo = (
            planner.MeshGeometry.from_mesh(mesh, axis=axis)
            if mesh is not None
            else None
        )
        if n_devices is None:
            n_devices = geo.model_size if geo is not None else jax.device_count()
        elif geo is not None and n_devices != geo.model_size:
            # an explicit n_devices overrides the mesh-derived model-axis
            # size in the cost model (legacy contract)
            geo = dataclasses.replace(geo, model_size=n_devices)
        committed_key = (
            tuple(sorted((k, v) for k, v in committed.items()))
            if committed
            else None
        )
        stats_key = _stats_key(stats)
        key = (
            mesh, axis, donate, mem_budget, n_devices, geo, committed_key,
            stats_key,
        )
        hit = self._compiled.get(key)
        if hit is not None:
            self._compiled.move_to_end(key)
            return hit

        with span(ENGINE_PLAN):
            # --- plan: the distribution planner picks a JoinPlan per join ----
            # (planner._rel_bytes reads sizes off relations whose payloads are
            # ShapeDtypeStructs, so the abstract env is a valid stats source)
            fwd_query = (
                self.program.forward
                if isinstance(self.program, GradientProgram)
                else self.program
            )
            plans = planner.plan_query(
                fwd_query,
                self.abstract_env,
                n_devices,
                mem_budget=mem_budget,
                geometry=geo,
                committed=committed,
                stats=stats,
            )
            input_specs = planner.input_pspecs(fwd_query, plans)

            # --- rechunk stage: relations whose committed layout is not the -
            # plan's grid get an explicit, costed re-blocking (the all-to-all
            # the bytes-moved model already charged via committed=): record
            # them so __call__ books the move as planned, not silent
            rechunks: Dict[str, int] = {}
            if committed and mesh is not None:
                for name, spec in committed.items():
                    planned = input_specs.get(name)
                    if _norm_spec(spec) != _norm_spec(planned):
                        rel = self.abstract_env.get(name)
                        rechunks[name] = (
                            int(planner._rel_bytes(rel))
                            if rel is not None
                            else 0
                        )

            # --- jit: plans become in_shardings, XLA inserts the collectives -
            engine = self.engine
            table = self.dispatch
            program = self.program

            jit_kwargs: Dict[str, Any] = (
                {"donate_argnums": (0,)} if donate else {}
            )
            shardings = None
            pad_nnz: Dict[str, int] = {}
            coo_pins: Dict[str, CooRelation] = {}
            if mesh is not None:
                sh_don: Dict[str, AnyRel] = {}
                sh_kept: Dict[str, AnyRel] = {}
                for k, rel in self.abstract_env.items():
                    sharding, pad = self._rel_sharding(
                        k, rel, input_specs.get(k), mesh
                    )
                    (sh_don if k in donate else sh_kept)[k] = sharding
                    if pad is not None:
                        pad_nnz[k] = pad
                    if isinstance(sharding, CooRelation) and tuple(
                        sharding.values.spec
                    ):
                        # nnz-sharded COO: pin the layout inside the jitted
                        # step too, so the traced segment-sum + scatter-add
                        # stays partitioned over the planned data axes (the
                        # per-shard local segsum + psum the plan costed)
                        # regardless of how XLA would re-place the operands.
                        coo_pins[k] = sharding
                jit_kwargs["in_shardings"] = (sh_don, sh_kept, None)
                shardings = (sh_don, sh_kept)

            def step(donated_env: Env, kept_env: Env, seed):
                env = dict(kept_env)
                env.update(donated_env)
                for name, sh in coo_pins.items():
                    rel = env[name]
                    env[name] = CooRelation(
                        jax.lax.with_sharding_constraint(rel.keys, sh.keys),
                        jax.lax.with_sharding_constraint(
                            rel.values, sh.values
                        ),
                        rel.extents,
                        rel.owner_dim,
                        rel.shard_offsets,
                    )
                return engine._execute(
                    env, seed, dispatch=table, program=program
                )

            compiled = Compiled(
                self,
                jax.jit(step, **jit_kwargs),
                donate,
                plans,
                input_specs,
                mesh,
                geo,
                shardings,
                pad_nnz,
                rechunks,
            )
        self._compiled[key] = compiled
        while len(self._compiled) > _MAX_COMPILED:
            self._compiled.popitem(last=False)
        return compiled

    def _relowered(self, table: kernels.DispatchTable) -> "Lowered":
        """This lowering re-resolved under another dispatch table: the same
        program, signature and validate report, with the kernel sites
        resolved (and recorded) afresh."""
        low = self._retabled.get(table)
        if low is None:
            resolutions = kernels.ResolutionLog()
            jax.eval_shape(
                functools.partial(
                    self.engine._execute,
                    dispatch=table,
                    resolutions=resolutions,
                    program=self.program,
                ),
                self.abstract_env,
                self.abstract_seed,
            )
            low = Lowered(
                self.engine,
                self.sig,
                table,
                self.abstract_env,
                self.abstract_seed,
                self.out_shape,
                resolutions,
                program=self.program,
                rewrite_report=self.rewrite_report,
                check_report=self.check_report,
            )
            self._retabled[table] = low
        return low

    def compile_auto(
        self,
        env: Env,
        *,
        mesh=None,
        axis: Optional[str] = None,
        donate: Tuple[str, ...] = (),
        mem_budget: float = planner.DEFAULT_MEM_BUDGET,
        stats: Optional[Dict[str, planner.RelationStats]] = None,
    ) -> Compiled:
        """``compile`` with committed layouts auto-threaded and a
        **plan-stability guarantee** — the PR-4 follow-up ("auto-thread
        committed layouts through the staged path without plan-flapping").

        The committed layouts of ``env``'s arrays are derived per call
        (``_committed_layouts``) and folded into planning, but the record
        of the plan last committed to is kept here: when every committed
        input already sits at that plan's own placement — the steady
        state once a step's outputs feed the next call — the recorded
        ``Compiled`` is returned as-is. First and later calls therefore
        produce the identical plan (bit-identical ``Compiled.plans``, the
        same executable, ``counters["reshard"]`` flat at zero moved bytes)
        instead of flapping between a no-committed and an all-committed
        plan. Only inputs committed to a genuinely *different* layout —
        an upstream producer changed its placement — trigger a re-plan,
        which then charges the rechunk and becomes the new record.

        This is the compile entry the ``Database`` session and the
        relational operator layer step through."""
        donate = tuple(sorted(donate))
        base = (mesh, axis, donate, mem_budget, _stats_key(stats))
        committed = _committed_layouts(env) if mesh is not None else {}
        prev = self._auto.get(base)
        if prev is not None and all(
            _norm_spec(prev.planned_spec(name)) == _norm_spec(spec)
            for name, spec in committed.items()
        ):
            self._auto.move_to_end(base)
            return prev
        compiled = self.compile(
            mesh=mesh,
            axis=axis,
            donate=donate,
            mem_budget=mem_budget,
            committed=committed or None,
            stats=stats,
        )
        self._auto[base] = compiled
        while len(self._auto) > _MAX_COMPILED:
            self._auto.popitem(last=False)
        return compiled

    @staticmethod
    def _rel_sharding(
        name: str, rel: AnyRel, spec: Optional[P], mesh
    ) -> Tuple[AnyRel, Optional[int]]:
        """Relation-shaped sharding pytree for one relation, plus the
        padded nnz row count when a COO's planned nnz sharding does not
        divide (pad-and-mask; ``None`` = no padding needed).

        Dense: the planner's block-axis spec, padded over chunk axes; a
        2-D plan's folded data-axis tuples (("pod", "data")) divide by
        the axes' product, and non-divisible extents fall back to
        replicating that dim with a structured ``ShardFallbackWarning``.

        COO: the planner's nnz spec (entry 0) lands on the keys/values
        row axis; a non-divisible row count is padded up to the next
        shard multiple rather than silently replicated."""
        sizes = dict(mesh.shape)

        def axes_total(ax) -> Optional[int]:
            axes = ax if isinstance(ax, tuple) else (ax,)
            if any(a not in sizes for a in axes):
                return None
            total = 1
            for a in axes:
                total *= int(sizes[a])
            return total

        if isinstance(rel, CooRelation):
            rep = NamedSharding(mesh, P())
            row_ax = tuple(spec)[0] if spec is not None and tuple(spec) else None
            total = axes_total(row_ax) if row_ax is not None else None
            if row_ax is None or total is None or total <= 1:
                return CooRelation(
                    rep, rep, rel.extents, rel.owner_dim, rel.shard_offsets
                ), None
            nnz = int(rel.keys.shape[0])
            pad = ((nnz + total - 1) // total) * total if nnz % total else None
            keys_sh = NamedSharding(mesh, P(row_ax, None))
            vals_sh = NamedSharding(
                mesh, P(row_ax, *([None] * (rel.values.ndim - 1)))
            )
            return CooRelation(
                keys_sh, vals_sh, rel.extents, rel.owner_dim, rel.shard_offsets
            ), pad

        full = [None] * len(rel.data.shape)
        if spec is not None:
            for d, ax in enumerate(tuple(spec)):
                if ax is None or d >= rel.key_arity:
                    continue
                total = axes_total(ax)
                if total is None:
                    continue
                if rel.data.shape[d] % total == 0:
                    full[d] = ax
                elif total > 1:
                    warnings.warn(
                        ShardFallbackWarning(
                            name, d, int(rel.data.shape[d]), total
                        ),
                        stacklevel=3,
                    )
        return DenseRelation(NamedSharding(mesh, P(*full)), rel.key_arity), None


# ---------------------------------------------------------------------------
# StreamedCompiled: out-of-core chunk-wave execution
# ---------------------------------------------------------------------------


class StreamedCompiled:
    """Chunk-wave executor for a ``planner.WavePlan``: the session's
    memory budget did not fit the environment, so the streamed relation
    (and its co-streams) live host-side in the ``ChunkStore`` and each
    call runs the normally-compiled step once per wave over ``resident +
    one chunk``, double-buffering the host→device transfer of wave
    ``w+1`` behind wave ``w``'s compute.

    Wave results merge by the plan's soundness analysis
    (``planner._stream_states``): an output leaf whose shape equals the
    full in-core lowering's expectation is an additive partial (Σ across
    waves — the loss, gradients of resident relations); a leaf whose
    shape differs along exactly one axis is wave-local rows of the
    streamed axis (gradients of the streamed relation itself) and is
    sliced to the wave's live rows — dropping the COO pad rows of the
    padded last chunk — and concatenated in row order. Either way the
    merged result equals the in-core step's.

    Duck-types ``Compiled`` for the session's introspection surface
    (``mesh``/``plans``/``placements``/``resolutions``/``counters``/
    ``planned_spec``) by delegating to the per-wave inner ``Compiled``
    (identical across waves of equal signature); ``planned_spec`` is None
    for streamed relations — they have no single device placement, so
    the catalog never commits a layout for them."""

    def __init__(self, plan, store, compile_wave, lower_full):
        from .chunkstore import OutOfCoreError  # noqa: F401  (re-raised)

        self.plan = plan
        self.store = store
        #: wave env → Compiled (the session's normal staged path; the
        #: engine's Lowered/Compiled caches make wave 2..n cache hits).
        self._compile_wave = compile_wave
        #: full env → Lowered (abstract shapes only — never executed):
        #: its out_shape is the merge oracle for ADD-vs-CONCAT leaves.
        self._lower_full = lower_full
        self._inner: Optional[Compiled] = None

    # -- Compiled surface ---------------------------------------------------

    @property
    def num_waves(self) -> int:
        return self.plan.num_waves

    @property
    def mesh(self):
        return self._inner.mesh if self._inner is not None else None

    @property
    def plans(self):
        return self._inner.plans if self._inner is not None else {}

    @property
    def placements(self):
        return self._inner.placements if self._inner is not None else {}

    @property
    def resolutions(self):
        return self._inner.resolutions if self._inner is not None else {}

    @property
    def counters(self) -> Dict[str, Dict[str, int]]:
        if self._inner is None:
            return {"reshard": {
                "calls": 0, "resharded_calls": 0, "bytes_moved": 0,
                "last_call_bytes": 0, "planned_bytes": 0,
            }}
        return self._inner.counters

    def planned_spec(self, name: str):
        if name in self.plan.streamed_names or self._inner is None:
            return None
        return self._inner.planned_spec(name)

    # -- execution ----------------------------------------------------------

    def _fetch_wave(self, resident: Env, w: int, max_rows: int) -> Env:
        """Resident relations + wave ``w``'s chunks, device-put issued
        (async) — calling this one wave ahead is the double buffer."""
        wave = dict(resident)
        for name in self.plan.streamed_names:
            rel = self.store.fetch(name, w)
            if isinstance(rel, CooRelation):
                # pad every COO wave to the largest chunk so all waves
                # share one env signature (one lowering, one executable);
                # pad rows carry COO_PAD_KEY and are sliced off on merge
                rel = pad_coo_nnz(rel, max_rows)
            wave[name] = rel
        return wave

    def _merge(self, wave_outs, want_shape):
        from .chunkstore import OutOfCoreError

        want_leaves, want_def = jax.tree_util.tree_flatten(want_shape)
        per_wave = [jax.tree_util.tree_leaves(o) for o in wave_outs]
        if any(len(p) != len(want_leaves) for p in per_wave):
            raise OutOfCoreError(
                "wave output structure does not match the in-core lowering"
            )
        bnd = self.plan.boundaries
        merged = []
        for i, want in enumerate(want_leaves):
            leaves = [p[i] for p in per_wave]
            wshape = tuple(want.shape)
            if all(tuple(g.shape) == wshape for g in leaves):
                out = leaves[0]
                for g in leaves[1:]:
                    out = out + g
                merged.append(out)
                continue
            shapes = {tuple(g.shape) for g in leaves}
            diff_axes = {
                ax
                for s in shapes
                if len(s) == len(wshape)
                for ax in range(len(s))
                if s[ax] != wshape[ax]
            }
            if len(diff_axes) != 1 or any(
                len(s) != len(wshape) for s in shapes
            ):
                raise OutOfCoreError(
                    f"cannot merge wave output leaf of shapes {shapes} "
                    f"into expected {wshape}: not an additive partial and "
                    "not single-axis wave rows"
                )
            ax = diff_axes.pop()
            cut = []
            for w, g in enumerate(leaves):
                rows = bnd[w + 1] - bnd[w]
                idx = [slice(None)] * g.ndim
                idx[ax] = slice(0, rows)  # drop COO pad rows of the wave
                # host-side assembly: the full-size streamed-axis result
                # is host-tier data by definition (it did not fit the
                # device budget), and np.asarray also canonicalizes
                # mesh-sharded wave leaves before the concat
                cut.append(np.asarray(jax.device_get(g[tuple(idx)])))
            merged.append(np.concatenate(cut, axis=ax))
        return jax.tree_util.tree_unflatten(want_def, merged)

    def __call__(self, env: Env, seed: Optional[AnyRel] = None):
        from .relation import ChunkManifest

        plan = self.plan
        streamed = set(plan.streamed_names)
        axis_of = dict(plan.axis_of)
        smani = ChunkManifest(
            axis=0,
            boundaries=plan.boundaries,
            owner_aligned=plan.owner_aligned,
        )
        self.store.spill(plan.stream, env[plan.stream], smani)
        for name in plan.co_streams:
            # co-streams share the stream's cut vector on their own axis:
            # wave w of the stream joins wave w of every co-stream
            self.store.spill(
                name,
                env[name],
                ChunkManifest(axis=axis_of[name], boundaries=plan.boundaries),
            )
        resident = {k: v for k, v in env.items() if k not in streamed}
        max_rows = smani.max_rows
        want_shape = self._lower_full(env, seed).out_shape

        outs = []
        wave = self._fetch_wave(resident, 0, max_rows)
        for w in range(plan.num_waves):
            if w + 1 < plan.num_waves:
                nxt = self._fetch_wave(resident, w + 1, max_rows)
            compiled = self._compile_wave(wave, seed)
            self._inner = compiled
            outs.append(compiled(wave, seed))
            if w + 1 < plan.num_waves:
                wave = nxt
        return self._merge(outs, want_shape)


# ---------------------------------------------------------------------------
# RAEngine: the wrapped program
# ---------------------------------------------------------------------------


class RAEngine:
    """Staged executor for an FRA query, bare gradient-graph root, or
    GradientProgram. Holds the lowering cache and the trace counter.

    This is the library-level staged executor; the ``repro.Database``
    session API (``db.query(...)`` / ``db.sql(...)``) layers the catalog
    — tracked statistics, committed layouts, the active mesh — on top of
    it and is the recommended front door for catalog-backed work."""

    def __init__(self, program: Program, *, fuse_join_agg: bool = True):
        self.source = program
        self.fuse_join_agg = fuse_join_agg
        #: number of actual FRA-graph walks (eager calls + jit traces).
        self.trace_count = 0
        self._lowered: Dict[Tuple, Lowered] = {}

        if isinstance(program, GradientProgram):
            self.kind = "grad"
            self.program = program
        elif isinstance(program, fra.Query):
            self.kind = "query"
            self.program = program
        elif isinstance(program, fra.Node):
            self.kind = "query"
            inputs = tuple(sorted({s.name for s in program.table_scans()}))
            self.program = fra.Query(program, inputs)
        else:
            raise TypeError(f"cannot wrap program of type {type(program)}")

    @property
    def forward_query(self) -> fra.Query:
        return (
            self.program.forward if self.kind == "grad" else self.program
        )

    # -- execution body (runs eagerly or under trace) ----------------------
    def _execute(
        self,
        env: Env,
        seed: Optional[AnyRel] = None,
        dispatch: Optional[kernels.DispatchTable] = None,
        resolutions: Optional[Dict[str, str]] = None,
        program: Optional[Program] = None,
    ):
        """Walk the program's FRA graph(s) over ``env`` (eagerly or under
        a jax trace). ``program`` overrides the engine's own program —
        the handle a ``Lowered`` uses to execute the *rewritten* program
        its cache entry lowered (core/rewrite.py) while sharing this
        engine's trace counter and fuse flag."""
        from . import compiler

        self.trace_count += 1
        prog = self.program if program is None else program
        if not isinstance(prog, GradientProgram):
            if seed is not None:
                raise ValueError("seed is only meaningful for GradientPrograms")
            return compiler._execute_graph(
                prog.root,
                env,
                fuse_join_agg=self.fuse_join_agg,
                dispatch=dispatch,
                resolutions=resolutions,
            )

        fwd_cache: Env = {}
        out = compiler._execute_graph(
            prog.forward.root,
            env,
            cache=fwd_cache,
            fuse_join_agg=self.fuse_join_agg,
            dispatch=dispatch,
            resolutions=resolutions,
        )
        if seed is None:
            if not (isinstance(out, DenseRelation) and out.key_arity == 0):
                raise ValueError("default seed requires a scalar-loss output")
            seed = DenseRelation(jnp.ones_like(out.data), key_arity=0)
        genv = dict(env)
        genv.update(fwd_cache)
        genv["__seed"] = seed
        # Gradient graphs fuse their own join-aggs regardless of how the
        # forward was executed (matches the historical grad_eval contract;
        # rjp_ablation relies on it).
        grads = {
            name: compiler._execute_graph(
                rootn, genv, dispatch=dispatch, resolutions=resolutions
            )
            for name, rootn in prog.grads.items()
        }
        return out, grads

    # -- the staged pipeline ----------------------------------------------
    def eager(
        self, env: Env, seed: Optional[AnyRel] = None, *, dispatch=None
    ):
        """Un-staged execution: walk the graph now, every call.
        ``dispatch`` takes anything ``kernels.make_table`` accepts."""
        table = kernels.make_table(dispatch)
        return self._execute(env, seed, dispatch=table)

    def lower(
        self,
        env: Env,
        seed: Optional[AnyRel] = None,
        *,
        dispatch=None,
        stats: Optional[Dict[str, planner.RelationStats]] = None,
        rewrite=None,
    ) -> Lowered:
        """Trace the chunked lowering at ``env``'s shapes under a kernel
        DispatchTable (``dispatch`` accepts anything ``kernels.make_table``
        does; None → backend default). Cached: a second call with an
        identical (signature, table, rewrite-key) triple returns the same
        Lowered without re-walking the graph; switching tiers is a cache
        miss and re-lowers.

        ``rewrite`` enables the cost-gated algebraic rewrite stage
        (core/rewrite.py) ahead of planning: anything
        ``rewrite.make_rules`` accepts — True for the default rule set, a
        ``RuleSet``, an iterable of rule names; None/False (default)
        skips the stage. ``stats`` is the catalog statistics snapshot the
        cost gate prices pushdowns with (also what sharpens the planner's
        estimates at compile time); its quantized form joins the enabled
        RuleSet in the cache key, so a statistics shift that could flip a
        gate re-lowers while same-bucket refreshes hit the cache. The
        rewritten program (and the gate report) live on the returned
        ``Lowered`` — declined rewrites keep the engine's original
        program object, bit-identical to a rewrite-off lowering."""
        table = kernels.make_table(dispatch)
        rules = _rewrite.make_rules(rewrite)
        rw_key = None if rules is None else (rules, _stats_key(stats))
        sig = env_signature(env, seed)
        key = (sig, table, rw_key)
        hit = self._lowered.get(key)
        if hit is not None:
            return hit
        with span(ENGINE_LOWER):
            # mandatory validate stage (repro.analysis.typecheck): schema/
            # shape/dtype-check the forward graph at this env's shapes before
            # the rewrite/plan/jit stages run — a malformed query fails here
            # with node-path diagnostics instead of a trace-time error from
            # deep inside the chunked lowering. Raises ValidationError on
            # error-severity findings; the full report (warnings included)
            # rides on the returned Lowered as ``check_report``.
            from ..analysis.typecheck import ValidationError, check_query

            check_report = check_query(
                self.forward_query, env, fuse_join_agg=self.fuse_join_agg
            )
            if not check_report.ok:
                raise ValidationError(check_report)
            abstract_env = {k: _abstract(v) for k, v in env.items()}
            abstract_seed = None if seed is None else _abstract(seed)
            program = None
            report = None
            if rules is not None:
                program, report = _rewrite.rewrite_program(
                    self.program, abstract_env, stats=stats, rules=rules
                )
            # a ResolutionLog (not a plain dict) so each dispatch decision
            # carries its site-info snapshot — analysis.kernelcheck replays
            # resolve_impl on the snapshots to certify the decisions stable
            resolutions: Dict[str, str] = kernels.ResolutionLog()
            out_shape = jax.eval_shape(
                functools.partial(
                    self._execute,
                    dispatch=table,
                    resolutions=resolutions,
                    program=program,
                ),
                abstract_env,
                abstract_seed,
            )
            low = Lowered(
                self,
                sig,
                table,
                abstract_env,
                abstract_seed,
                out_shape,
                resolutions,
                program=program,
                rewrite_report=report,
                check_report=check_report,
            )
        self._lowered[key] = low
        return low


# ---------------------------------------------------------------------------
# Module-level engine registry + one-call convenience
# ---------------------------------------------------------------------------

_ENGINES: "OrderedDict[Tuple[int, bool], RAEngine]" = OrderedDict()
_MAX_ENGINES = 256

#: ambient-mesh stack; a ContextVar so concurrent threads / tasks (e.g. a
#: serving worker pool) each see only their own mesh-context nesting.
_MESH_STACK: "contextvars.ContextVar[Tuple[Any, ...]]" = contextvars.ContextVar(
    "repro_engine_mesh_stack", default=()
)


@contextlib.contextmanager
def _use_mesh(mesh):
    """Internal ambient-mesh context (no deprecation warning): pushes
    ``mesh`` — a jax Mesh or a ``launch/mesh.resolve_mesh`` spec string —
    onto the stack ``default_mesh`` reads. ``Database.activate`` uses
    this to make the session's active mesh ambient for the relational
    operator layer."""
    if isinstance(mesh, str):
        from repro.launch.mesh import resolve_mesh

        mesh = resolve_mesh(mesh)
    token = _MESH_STACK.set(_MESH_STACK.get() + (mesh,))
    try:
        yield mesh
    finally:
        _MESH_STACK.reset(token)


def default_mesh():
    """The innermost ambient (``_use_mesh`` / session-activated) mesh,
    or None."""
    stack = _MESH_STACK.get()
    return stack[-1] if stack else None


def _committed_layouts(env: Env) -> Dict[str, P]:
    """PartitionSpec per relation whose arrays are *committed* to a
    NamedSharding layout (outputs of earlier compiled steps; explicitly
    device_put inputs) — the dict ``Lowered.compile(committed=...)``
    expects, so the planner charges device-layout rechunks instead of
    ``Compiled.__call__`` silently paying them. Uncommitted (freshly
    created) arrays place for free and are omitted."""
    out: Dict[str, P] = {}
    for name, rel in env.items():
        arr = rel.data if isinstance(rel, DenseRelation) else rel.values
        sh = getattr(arr, "sharding", None)
        if (
            getattr(arr, "committed", False)
            and isinstance(sh, NamedSharding)
        ):
            out[name] = sh.spec
    return out


def engine_for(program: Program, *, fuse_join_agg: bool = True) -> RAEngine:
    """Engine per (program identity, fuse flag), LRU-bounded. The engine
    holds a strong reference to the program, so the id key cannot be
    recycled while the entry lives. This is the internal registry the
    ``Database`` session steps through."""
    key = (id(program), fuse_join_agg)
    eng = _ENGINES.get(key)
    if eng is not None and eng.source is program:
        _ENGINES.move_to_end(key)
        return eng
    eng = RAEngine(program, fuse_join_agg=fuse_join_agg)
    _ENGINES[key] = eng
    while len(_ENGINES) > _MAX_ENGINES:
        _ENGINES.popitem(last=False)
    return eng


def _trace_clean(*trees) -> bool:
    """True when no leaf of ``trees`` (a step's inputs) is a jax Tracer,
    i.e. the step is not being traced by an outer jit/grad. Meshes are
    only compiled against outside such traces: an outer trace's in-flight
    shardings would fight the planner's, so sharding is left to propagate
    from the traced operands instead. The one place this probe lives —
    the session's mesh resolution reuses it."""
    return not any(
        isinstance(x, jax.core.Tracer) for x in jax.tree_util.tree_leaves(trees)
    )


def _ambient_mesh(*trees):
    """The mesh a staged execution over ``trees`` should compile against:
    the innermost ambient mesh, or None when the inputs are traced."""
    return default_mesh() if _trace_clean(*trees) else None


def _staged_execute(
    program: Program,
    env: Env,
    seed: Optional[AnyRel] = None,
    *,
    mesh=None,
    donate: Tuple[str, ...] = (),
    fuse_join_agg: bool = True,
    dispatch=None,
    stats: Optional[Dict[str, planner.RelationStats]] = None,
    mem_budget: float = planner.DEFAULT_MEM_BUDGET,
    rewrite=None,
):
    """lower → plan → compile → run in one call, with every stage cached:
    per-program engine, per-(signature, dispatch-table, rewrite-key)
    Lowered, per-mesh ``compile_auto`` record (committed layouts folded
    without plan-flapping). The internal staged hot path
    ``Database.execute`` and the relational operator layer step through;
    ``mesh=None`` picks up the ambient (session-activated) mesh when the
    inputs are not traced; ``rewrite`` enables the cost-gated rewrite stage (anything
    ``rewrite.make_rules`` accepts)."""
    if mesh is None:
        mesh = _ambient_mesh(env, seed)
    eng = engine_for(program, fuse_join_agg=fuse_join_agg)
    compiled = eng.lower(
        env, seed, dispatch=dispatch, stats=stats, rewrite=rewrite
    ).compile_auto(
        env, mesh=mesh, donate=donate, stats=stats, mem_budget=mem_budget
    )
    return compiled(env, seed)
