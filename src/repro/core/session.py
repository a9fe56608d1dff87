"""Database session: the one front door from SQL to compiled gradient step.

The paper's pitch is that ML *is* a relational computation run by a
database engine — so the user-facing surface should look like a database,
not like a bag of engine internals. ``Database`` (re-exported as
``repro.Database``) is that surface: a session object owning the
**catalog** a real relational system keeps —

  * named relations with schemas (key attribute names),
  * **tracked key-domain statistics** per relation
    (``planner.RelationStats``: distinct key counts, key-domain extents,
    nnz/density for COO layouts), refreshed on ``db.put`` and cheap to
    snapshot,
  * the physical layout each compiled plan committed a relation to,
  * the active mesh and the kernel dispatch table

— and one coherent query path::

    db = repro.Database(mesh="host:2")
    db.put("Rx", X, keys=("row", "col"))
    db.put("Ry", y, keys=("row",))
    db.put("theta", theta, keys=("col",))
    handle = db.sql(LOGREG_SQL, wrt=("theta",))   # or db.query(fra_query)
    loss = handle.forward()
    grads = handle.grad()                         # RA-autodiff, compiled
    loss, grads = handle.step(donate=("theta",))  # the training hot path

``forward`` / ``grad`` / ``step`` all lower → plan → compile through the
staged engine (core/engine.py), but source *everything the planner
needs* from the catalog: relation environments by name, the statistics
snapshot that replaces the planner's Agg-size / edge-cut heuristics, the
session mesh, the dispatch table, and the committed-layout record that
guarantees plan stability across calls (``Lowered.compile_auto``).

Sessions also run the cost-gated algebraic rewrite stage
(core/rewrite.py) ahead of planning — Σ-through-⋈ pushdown, Σ-split,
common-subplan dedup, each priced against the catalog's tracked
statistics — and ``db.explain(query)`` shows the before/after trees with
every gate verdict.
"""

from __future__ import annotations

import contextlib
import contextvars
import copy
import warnings
import weakref
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import chunkstore as _chunkstore
from . import engine as _engine
from . import fra, kernels, planner
from . import rewrite as _rewrite
from . import sql as _sql
from .autodiff import GradientProgram, ra_autodiff
from .relation import CooRelation, DenseRelation, measure_stats
from .spans import (
    ENGINE_LOOKUP,
    SESSION_PUT,
    SESSION_STATS,
    SESSION_STEP,
    span,
    spanned,
)

AnyRel = Union[DenseRelation, CooRelation]


class CatalogError(KeyError):
    """A query referenced a relation the session's catalog does not hold
    (or holds in an unusable state, e.g. donated to a compiled step)."""

    def __str__(self) -> str:  # KeyError repr()s its args; keep prose
        return self.args[0] if self.args else ""


@dataclass
class TableEntry:
    """One catalog row: a named relation plus everything the optimizer
    and the SQL frontend know about it."""

    name: str
    relation: AnyRel
    #: key attribute names (the SQL schema; positional order = key dims).
    key_attrs: Tuple[str, ...]
    #: tracked key-domain statistics (refreshed on ``Database.put``).
    stats: planner.RelationStats
    #: the PartitionSpec the last compiled plan committed this relation
    #: to (None until a mesh-compiled step placed it).
    layout: Optional[Any] = None
    #: True once the relation's buffers were donated to a compiled step —
    #: the entry must be re-``put`` before it can be read again.
    donated: bool = False


@dataclass
class ModelEntry:
    """One row of the catalog's model registry: a served model under a
    ``name@version`` coordinate. The serving front door
    (``Database.endpoint``) resolves every request — including per-tenant
    aliases — through these entries, so re-registering a version swaps
    the served parameters without touching the endpoint."""

    name: str
    version: str
    model: Any
    params: Any

    @property
    def key(self) -> Tuple[str, str]:
        return (self.name, self.version)

    def __str__(self) -> str:
        return f"{self.name}@{self.version}"


class Catalog:
    """Named relations + schemas + statistics + committed layouts — the
    structure a database optimizer consults on every query — plus the
    model registry the serving front door resolves requests through."""

    def __init__(self) -> None:
        self._tables: "OrderedDict[str, TableEntry]" = OrderedDict()
        #: name → version → ModelEntry (insertion order; last = latest).
        self._models: "OrderedDict[str, OrderedDict[str, ModelEntry]]" = (
            OrderedDict()
        )

    def __contains__(self, name: str) -> bool:
        return name in self._tables

    def __iter__(self) -> Iterator[str]:
        return iter(self._tables)

    def __len__(self) -> int:
        return len(self._tables)

    def entry(self, name: str) -> TableEntry:
        try:
            return self._tables[name]
        except KeyError:
            raise CatalogError(
                f"relation {name!r} is not in the catalog "
                f"(tables: {sorted(self._tables)}); db.put(...) it first"
            ) from None

    def items(self):
        return self._tables.items()

    def put(
        self,
        name: str,
        relation: AnyRel,
        key_attrs: Optional[Sequence[str]] = None,
        *,
        refresh_stats: bool = True,
    ) -> TableEntry:
        prev = self._tables.get(name)
        if key_attrs is None:
            if prev is not None and len(prev.key_attrs) == relation.key_arity:
                key_attrs = prev.key_attrs  # keep the declared schema
            else:
                key_attrs = tuple(f"k{i}" for i in range(relation.key_arity))
        key_attrs = tuple(key_attrs)
        if len(key_attrs) != relation.key_arity:
            raise ValueError(
                f"relation {name!r}: {len(key_attrs)} key attribute name(s) "
                f"{key_attrs} for key arity {relation.key_arity}"
            )
        if refresh_stats or prev is None:
            with span(SESSION_STATS):
                stats = measure_stats(relation)
        else:
            stats = prev.stats
        entry = TableEntry(name, relation, key_attrs, stats)
        if prev is not None:
            entry.layout = prev.layout
        self._tables[name] = entry
        return entry

    def drop(self, name: str) -> None:
        self._tables.pop(name, None)

    def schema(self) -> Dict[str, Tuple[str, ...]]:
        """{relation: key attribute names} — what ``compile_sql`` takes."""
        return {n: e.key_attrs for n, e in self._tables.items()}

    def snapshot(
        self, names: Optional[Sequence[str]] = None
    ) -> Dict[str, planner.RelationStats]:
        """Cheap, hashable statistics snapshot for the planner (the
        ``stats=`` argument of ``plan_query`` / ``Lowered.compile``).
        ``names`` restricts the snapshot to the given relations — query
        handles pass their own base relations so that the snapshot (a
        compile cache key component) is insensitive to updates of
        unrelated catalog tables."""
        if names is None:
            return {n: e.stats for n, e in self._tables.items()}
        return {
            n: self._tables[n].stats for n in names if n in self._tables
        }

    def record_layout(self, name: str, spec) -> None:
        e = self._tables.get(name)
        if e is not None:
            e.layout = spec

    # -- model registry (the serving front door resolves through this) -----

    def put_model(
        self, name: str, model, params, version: Optional[str] = None
    ) -> ModelEntry:
        """Register (or update) a served model version. ``version``
        defaults to ``v<n+1>``; re-registering an existing version swaps
        its model/params in place (live endpoints pick the new parameters
        up on the next batch they form)."""
        versions = self._models.setdefault(name, OrderedDict())
        if version is None:
            version = f"v{len(versions) + 1}"
        entry = ModelEntry(name, str(version), model, params)
        versions[entry.version] = entry
        versions.move_to_end(entry.version)
        return entry

    def model(self, name: str, version: Optional[str] = None) -> ModelEntry:
        """Resolve ``name[@version]`` to a registered ModelEntry (latest
        registered version when ``version`` is None)."""
        if version is None and "@" in name:
            name, _, version = name.partition("@")
        try:
            versions = self._models[name]
        except KeyError:
            raise CatalogError(
                f"model {name!r} is not registered (models: "
                f"{sorted(self._models)}); db.register_model(...) it first"
            ) from None
        if version is None:
            return next(reversed(versions.values()))
        try:
            return versions[str(version)]
        except KeyError:
            raise CatalogError(
                f"model {name!r} has no version {version!r} "
                f"(versions: {list(versions)})"
            ) from None

    def models(self) -> Dict[str, Tuple[str, ...]]:
        """{model name: registered versions, oldest→latest}."""
        return {n: tuple(v) for n, v in self._models.items()}


# ---------------------------------------------------------------------------
# The session
# ---------------------------------------------------------------------------

#: ambient session stack (ContextVar: concurrent threads/tasks see only
#: their own ``Database.activate`` nesting), plus one lazily created
#: process-default session for the relational operator layer.
_SESSION_STACK: "contextvars.ContextVar[Tuple[Database, ...]]" = (
    contextvars.ContextVar("repro_session_stack", default=())
)
_PROCESS_DEFAULT: Optional["Database"] = None


def current() -> "Database":
    """The ambient session: the innermost ``Database.activate`` block's
    session, else a process-wide default ``Database()``. The relational
    operator layer (``rel_matmul``, ``gcn_conv``, ``rel_embed``) steps
    through this, so activating a session distributes those ops on its
    mesh without new arguments crossing the ``custom_vjp`` boundary."""
    stack = _SESSION_STACK.get()
    if stack:
        return stack[-1]
    global _PROCESS_DEFAULT
    if _PROCESS_DEFAULT is None:
        _PROCESS_DEFAULT = Database()
    return _PROCESS_DEFAULT


#: field layout of the per-executable reshard counters (engine.Compiled).
_RESHARD_KEYS = (
    "calls", "resharded_calls", "bytes_moved", "last_call_bytes",
    "planned_bytes",
)


def _serve_counters() -> Dict[str, Any]:
    """Zeroed ``serve/`` subtree of the unified counter tree — the async
    serving front door (serving/service.py) increments these."""
    return {
        "requests": 0,        # submitted to an endpoint on this session
        "admitted": 0,        # passed the bounded admission queue
        "completed": 0,       # futures resolved with a Completion
        "failed": 0,          # futures resolved with an error
        "shed_queue_full": 0,  # rejected: admission queue at max_queue
        "shed_deadline": 0,    # rejected: deadline passed before service
        "batches": 0,          # coalesced prefill batches executed
        "batched_requests": 0,  # requests that shared a batch (size > 1)
        "queue_peak": 0,       # high-water admission queue depth
        "prefill": {"compiles": 0, "steps": 0},
        "decode": {
            "compiles": 0,      # decode executables built (per bucket)
            "traces": 0,        # decode retraces (≤ one per bucket)
            "steps": 0,         # decode steps executed
            "rebuckets": 0,     # mid-decode compactions to a smaller bucket
            "slot_releases": 0,  # slots freed by finished requests
            "eos_stops": 0,      # slots released early on an EOS token
        },
    }


class Database:
    """A session: catalog + statistics + active mesh + dispatch table,
    and the one query path from SQL (or FRA) to a compiled gradient step.

    ``mesh`` is a jax Mesh, a ``launch/mesh.resolve_mesh`` spec string
    (``"host"``, ``"host:<model>"``, ``"production"``,
    ``"production:multipod"``), or None (single-device; an ambient
    session mesh still applies). ``dispatch`` takes anything
    ``kernels.make_table`` accepts and pins the kernel tier for every
    query compiled in this session. ``rewrite`` configures the
    cost-gated algebraic rewrite stage run ahead of planning (anything
    ``rewrite.make_rules`` accepts: True — the default — enables the
    full rule set, False disables the stage, a ``rewrite.RuleSet`` or an
    iterable of rule names selects rules). ``max_cache_entries`` bounds
    the session's executable cache (LRU) — the serving batch cache rides
    on it; None = unbounded.
    """

    def __init__(
        self,
        mesh=None,
        *,
        dispatch=None,
        mem_budget: Optional[float] = None,
        memory_budget: Optional[float] = None,
        fuse_join_agg: bool = True,
        rewrite=True,
        max_cache_entries: Optional[int] = None,
    ) -> None:
        self.catalog = Catalog()
        #: the session's enabled rewrite rules (None = stage off).
        self.rewrite_rules = _rewrite.make_rules(rewrite)
        self._mesh_spec = mesh
        self._mesh_resolved = mesh is None or not isinstance(mesh, str)
        self._mesh = None if isinstance(mesh, str) else mesh
        self.dispatch = kernels.make_table(dispatch)
        self.mem_budget = (
            planner.DEFAULT_MEM_BUDGET if mem_budget is None else mem_budget
        )
        #: out-of-core *device-memory* budget in bytes (distinct from
        #: ``mem_budget``, the planner's per-device plan-feasibility
        #: budget): when a step's environment exceeds it, the largest
        #: base relation is spilled to the host-resident ChunkStore and
        #: streamed through the step in chunk waves. None (default)
        #: disables spilling entirely — plans and results are
        #: bit-identical to an unbudgeted session.
        self.memory_budget = memory_budget
        self._chunkstore = _chunkstore.ChunkStore()
        self.fuse_join_agg = fuse_join_agg
        self.max_cache_entries = max_cache_entries
        self._exec_cache: "OrderedDict[Any, Any]" = OrderedDict()
        #: the session's unified telemetry tree (``db.counters()``); the
        #: ``cache`` and ``serve`` subtrees live here, ``reshard`` is
        #: aggregated over compiled executables and ``spill`` read off the
        #: ChunkStore at snapshot time.
        self._counters: Dict[str, Any] = {
            "cache": {"hits": 0, "misses": 0, "evictions": 0},
            "serve": _serve_counters(),
        }
        #: every executable this session compiled (weak — engine caches
        #: keep live ones alive), for the reshard counter aggregate.
        self._compiled_refs: "weakref.WeakSet" = weakref.WeakSet()

    # -- catalog front door ------------------------------------------------

    @spanned(SESSION_PUT)
    def put(
        self,
        name: str,
        value,
        *,
        keys: Optional[Sequence[str]] = None,
        key_arity: Optional[int] = None,
        refresh_stats: bool = True,
    ) -> "Database":
        """Register (or update) a named relation and refresh its tracked
        statistics. ``value`` is a relation, or a raw array made into a
        ``DenseRelation`` whose key arity is ``len(keys)`` (or
        ``key_arity``) — the leading dims are the key grid, the rest the
        tuple chunk::

            db.put("Rx", X, keys=("row", "col"))     # (n, m) array
            db.put("Edge", coo_relation)             # relation as-is

        ``refresh_stats=False`` keeps the previous statistics (skip the
        COO distinct-count pass when only values changed). Returns the
        session for chaining."""
        if not isinstance(value, (DenseRelation, CooRelation)):
            arr = jnp.asarray(value)
            if keys is not None:
                arity = len(tuple(keys))
            elif key_arity is not None:
                arity = key_arity
            else:
                arity = arr.ndim
            value = DenseRelation(arr, arity)
        if (
            self.memory_budget is not None
            and planner._rel_bytes(value) > self.memory_budget
        ):
            # host tier: a relation bigger than the device budget is kept
            # as host numpy — statistics, signatures and abstract lowering
            # all work on numpy payloads, and the wave executor splits
            # host-side anyway, so nothing forces it onto the device
            if isinstance(value, DenseRelation):
                value = DenseRelation(np.asarray(value.data), value.key_arity)
            else:
                value = CooRelation(
                    np.asarray(value.keys),
                    np.asarray(value.values),
                    value.extents,
                    value.owner_dim,
                    value.shard_offsets,
                )
        self.catalog.put(name, value, keys, refresh_stats=refresh_stats)
        return self

    def get(self, name: str) -> AnyRel:
        """The named relation (raises ``CatalogError`` when absent or
        when its buffers were donated to a compiled step)."""
        e = self.catalog.entry(name)
        if e.donated:
            raise CatalogError(
                f"relation {name!r} was donated to a compiled step; "
                f"db.put(...) its updated value before reading it again"
            )
        return e.relation

    def __contains__(self, name: str) -> bool:
        return name in self.catalog

    def drop(self, name: str) -> None:
        self.catalog.drop(name)

    def stats(self, name: str) -> planner.RelationStats:
        """The tracked key-domain statistics of one relation."""
        return self.catalog.entry(name).stats

    def schema(self, name: str) -> Tuple[str, ...]:
        """The key attribute names of one relation."""
        return self.catalog.entry(name).key_attrs

    def layout(self, name: str):
        """The PartitionSpec the last compiled plan committed the
        relation to (None before any mesh-compiled step)."""
        return self.catalog.entry(name).layout

    # -- model registry + the serving front door ---------------------------

    def register_model(
        self, name: str, model, params, *, version: Optional[str] = None
    ) -> ModelEntry:
        """Register a model version in the catalog's model registry —
        what the serving front door (``db.endpoint``) resolves request
        model/tenant coordinates through. ``version`` defaults to
        ``v<n+1>``; re-registering a version hot-swaps its parameters
        (live endpoints serve the new ones from the next batch on)."""
        return self.catalog.put_model(name, model, params, version)

    def model(self, name: str, version: Optional[str] = None) -> ModelEntry:
        """Resolve ``name`` (or ``"name@version"``) from the model
        registry — latest registered version when unversioned."""
        return self.catalog.model(name, version)

    def endpoint(self, model=None, **kwargs) -> "Any":
        """The serving front door: an async ``Endpoint`` over this
        session — continuous batching of concurrent requests into the
        session's (batch, seq) bucketed executables, decode-step
        bucketing, per-tenant model versions resolved through the
        catalog's model registry, and bounded-queue/deadline load
        shedding counted under ``db.counters()["serve"]``.

        ``model`` is a registered model name (``"lm"`` / ``"lm@v2"``) or
        a Model instance (auto-registered; pass ``params=``). See
        ``repro.serving.service.Endpoint`` for the keyword surface
        (``cache_len``, ``buckets``, ``decode_buckets``, ``max_queue``,
        ``gather_window``, ...)."""
        from repro.serving.service import Endpoint

        return Endpoint(self, model, **kwargs)

    # -- unified telemetry -------------------------------------------------

    def counters(self) -> Dict[str, Any]:
        """The session's telemetry tree — **one** structured surface for
        every counter the stack keeps, snapshotted (mutating the returned
        dict never touches live state)::

            {"cache":   {hits, misses, evictions},          # exec cache
             "reshard": {calls, resharded_calls, bytes_moved,
                         last_call_bytes, planned_bytes},   # aggregated
                                                            # over every
                                                            # compiled step
             "spill":   {spilled_relations, spilled_bytes,
                         fetched_chunks, fetched_bytes},    # out-of-core
             "serve":   {requests, admitted, completed, failed,
                         shed_queue_full, shed_deadline, batches,
                         batched_requests, queue_peak,
                         prefill: {compiles, steps},
                         decode:  {compiles, traces, steps, rebuckets,
                                   slot_releases, eos_stops}}}

        ``reshard`` sums the per-executable counters of every step this
        session compiled (``Compiled.counters["reshard"]``);
        ``last_call_bytes`` sums each live executable's most recent
        call. This is the single telemetry surface — the pre-unification
        accessors (``cache_stats``/``spill_stats``/``reshard_stats``)
        are gone."""
        reshard = dict.fromkeys(_RESHARD_KEYS, 0)
        for comp in list(self._compiled_refs):
            for k, v in comp.counters["reshard"].items():
                reshard[k] = reshard.get(k, 0) + v
        return {
            "cache": dict(self._counters["cache"]),
            "reshard": reshard,
            "spill": dict(self._chunkstore.stats),
            "serve": copy.deepcopy(self._counters["serve"]),
        }

    def resolutions(self) -> Dict[str, Tuple[str, ...]]:
        """``op[site] → tiers``: the sorted set of tiers each dispatch
        site resolved to, over every live step this session compiled —
        query handles' steps and the anonymous-environment steps of the
        relational operator layer alike. Steps can share a site key and
        still resolve it differently (one on a mesh, one not), so a key
        may carry more than one tier (``QueryHandle.resolutions`` covers
        one handle's last step)."""
        out: Dict[str, set] = {}
        for comp in list(self._compiled_refs):
            for site, tier in comp.resolutions.items():
                out.setdefault(site, set()).add(tier)
        return {site: tuple(sorted(t)) for site, t in sorted(out.items())}

    # -- the active mesh ---------------------------------------------------

    @property
    def mesh(self):
        """The session's active mesh (spec strings resolved lazily, so
        constructing a Database never touches jax device state)."""
        if not self._mesh_resolved:
            from repro.launch.mesh import resolve_mesh

            self._mesh = resolve_mesh(self._mesh_spec)
            self._mesh_resolved = True
        return self._mesh

    def use_mesh(self, mesh) -> "Database":
        """Re-point the session at a different mesh (spec string or jax
        Mesh). Compiled plans are cached per mesh, so switching back is
        cheap."""
        self._mesh_spec = mesh
        self._mesh_resolved = mesh is None or not isinstance(mesh, str)
        self._mesh = None if isinstance(mesh, str) else mesh
        return self

    def _step_mesh(self, *trees):
        """Mesh a step over inputs ``trees`` should compile against: the
        session mesh, traced inputs or not (a step inside a caller's
        trace places no inputs, ``Lowered.compile(traced=True)``); else
        the ambient mesh of an enclosing activated session when the
        inputs are concrete, None when an outer trace is tracing them
        (the engine's ``_trace_clean`` probe is the single source of that
        rule)."""
        if self.mesh is not None:
            return self.mesh
        return _engine._ambient_mesh(*trees)

    @contextlib.contextmanager
    def activate(self):
        """Make this the ambient session of the block: the relational
        operator layer (and any code calling ``session.current()``)
        plans, dispatches and distributes through it."""
        token = _SESSION_STACK.set(_SESSION_STACK.get() + (self,))
        try:
            yield self
        finally:
            _SESSION_STACK.reset(token)

    # -- query front door --------------------------------------------------

    def sql(self, script: str, *, wrt: Sequence[str] = ()) -> "QueryHandle":
        """Compile a SQL script against the catalog's schemas and return
        a differentiable ``QueryHandle``. ``wrt`` names the relations to
        treat as differentiable inputs (everything else is constant
        data); table and column references resolve against the key
        attribute names declared via ``db.put(..., keys=...)``."""
        query = _sql.compile_sql(
            script, schema=self.catalog.schema(), inputs=tuple(wrt)
        )
        return QueryHandle(self, query)

    def query(
        self, q: Union[fra.Query, fra.Node], *, wrt: Optional[Sequence[str]] = None
    ) -> "QueryHandle":
        """Wrap an FRA query (or bare graph root) built in code. ``wrt``
        defaults to the query's declared inputs (for a bare node: its
        table scans)."""
        if isinstance(q, fra.Node):
            inputs = tuple(sorted({s.name for s in q.table_scans()}))
            q = fra.Query(q, inputs)
        if wrt is not None:
            missing = set(wrt) - set(q.inputs)
            if missing:
                raise ValueError(
                    f"wrt relations {sorted(missing)} are not inputs of the "
                    f"query (inputs: {q.inputs})"
                )
        return QueryHandle(self, q, default_wrt=None if wrt is None else tuple(wrt))

    def check(self, q: Union[fra.Query, fra.Node], *, wrt: Sequence[str] = ()):
        """Statically check an FRA query (or bare graph root) against the
        catalog: the typed checker (``repro.analysis.typecheck``) infers
        schemas/shapes/dtypes bottom-up and returns a ``CheckReport`` of
        node-path diagnostics — compiler-guaranteed failures as errors
        (bad join keys, non-permutation σ, non-additive Σ, COO ⋈ COO...),
        hazards as warnings (f32→f64 promotion, statically empty
        selections, stale statistics, non-divisible sharded extents,
        partial-RJP gradients for ``wrt`` inputs). Relations, statistics,
        key-attribute names and the mesh geometry are sourced from the
        catalog exactly as a compiled step would source them. Purely
        observational — nothing is lowered or cached; the same checker
        runs as the engine's mandatory validate stage, which *raises* on
        the error-severity findings reported here."""
        from repro.analysis.typecheck import check_query

        if isinstance(q, fra.Node):
            q = fra.Query(
                q, tuple(sorted({s.name for s in q.table_scans()}))
            )
        names = _base_names([q.root])
        env = {
            n: self.catalog.entry(n).relation
            for n in names
            if n in self.catalog
        }
        stats = self.catalog.snapshot(names)
        schema = self.catalog.schema()
        mesh = self.mesh
        geometry = (
            planner.MeshGeometry.from_mesh(mesh) if mesh is not None else None
        )
        return check_query(
            q,
            env,
            stats=stats,
            schema=schema,
            geometry=geometry,
            wrt=tuple(wrt),
            fuse_join_agg=self.fuse_join_agg,
        )

    def explain(self, q: Union[fra.Query, fra.Node]) -> str:
        """What the rewrite stage would do to ``q`` against the current
        catalog: the query tree before, every cost-gate verdict (with the
        byte estimates the gate compared), and the tree after. Relations
        and their tracked statistics are sourced from the catalog exactly
        as ``forward``/``grad``/``step`` would source them, so the
        verdicts shown are the ones a compiled step takes. Observational
        with one exception: when the typed check is clean the query is
        *lowered* (never planned or executed) so the kernel certifier
        (``repro.analysis.kernelcheck``) can prove the exact dispatch
        sites the plan resolved — the Lowered and its certification
        report land in the engine's ordinary caches, which a later
        ``forward``/``grad``/``step`` reuses."""
        if isinstance(q, fra.Node):
            q = fra.Query(
                q, tuple(sorted({s.name for s in q.table_scans()}))
            )
        names = _base_names([q.root])
        env = {n: self.get(n) for n in names}
        stats = self.catalog.snapshot(names)
        rules = (
            self.rewrite_rules
            if self.rewrite_rules is not None
            else _rewrite.DEFAULT_RULES
        )
        rewritten, report = _rewrite.rewrite_query(
            q, env, stats=stats, rules=rules
        )
        lines = ["before:"]
        lines += ["  " + ln for ln in q.root.pretty().splitlines()]
        lines.append("rewrite decisions:")
        lines += ["  " + ln for ln in report.render().splitlines()]
        if self.rewrite_rules is None:
            lines.append("  (session rewrite stage is OFF: plan unchanged)")
            lines.append("after: (unchanged)")
        elif not report.changed:
            lines.append("after: (unchanged)")
        else:
            lines.append("after:")
            lines += [
                "  " + ln for ln in rewritten.root.pretty().splitlines()
            ]
        lines.append("diagnostics:")
        report = self.check(q)
        if report.diagnostics:
            lines += ["  " + ln for ln in report.render().splitlines()]
        else:
            lines.append("  (none)")
        lines.append("kernel certification:")
        if report.errors:
            lines.append("  (skipped: typed check failed)")
        else:
            from repro.analysis import kernelcheck as _kernelcheck

            eng = _engine.engine_for(q, fuse_join_agg=self.fuse_join_agg)
            low = eng.lower(
                env,
                dispatch=self.dispatch,
                stats=stats,
                rewrite=self.rewrite_rules,
            )
            kreport = _kernelcheck.certify_kernels(low)
            sites = len(getattr(low.resolutions, "sites", ()))
            lines.append(
                f"  {sites} dispatch site(s): " + kreport.render().splitlines()[0]
            )
            if kreport.diagnostics:
                lines += [
                    "  " + ln for ln in kreport.render().splitlines()[1:]
                ]
        return "\n".join(lines)

    # -- staged execution (the engine underneath) --------------------------

    @spanned(ENGINE_LOOKUP)
    def _compiled_for(
        self,
        program,
        env: Dict[str, AnyRel],
        seed: Optional[AnyRel] = None,
        *,
        donate: Tuple[str, ...] = (),
        stats: Optional[Dict[str, planner.RelationStats]] = None,
    ):
        eng = _engine.engine_for(program, fuse_join_agg=self.fuse_join_agg)
        if self.memory_budget is not None:
            fwd = eng.forward_query
            wave_plan = planner.plan_waves(fwd, env, self.memory_budget)
            if wave_plan is not None:
                if donate:
                    raise _chunkstore.OutOfCoreError(
                        f"cannot donate {sorted(donate)} while streaming "
                        "chunk waves: the buffers are reused across waves"
                    )

                def compile_wave(wave_env, wave_seed):
                    wstats = self._catalog_stats_for(wave_env)
                    wlow = eng.lower(
                        wave_env,
                        wave_seed,
                        dispatch=self.dispatch,
                        stats=wstats,
                        rewrite=self.rewrite_rules,
                    )
                    return wlow.compile_auto(
                        wave_env,
                        mesh=self._step_mesh(wave_env, wave_seed),
                        stats=wstats,
                        mem_budget=self.mem_budget,
                    )

                def lower_full(full_env, full_seed):
                    return eng.lower(
                        full_env,
                        full_seed,
                        dispatch=self.dispatch,
                        stats=stats,
                        rewrite=self.rewrite_rules,
                    )

                streamed = _engine.StreamedCompiled(
                    wave_plan, self._chunkstore, compile_wave, lower_full
                )
                self._compiled_refs.add(streamed)
                return streamed
        low = eng.lower(
            env,
            seed,
            dispatch=self.dispatch,
            stats=stats,
            rewrite=self.rewrite_rules,
        )
        compiled = low.compile_auto(
            env,
            mesh=self._step_mesh(env, seed),
            donate=donate,
            stats=stats,
            mem_budget=self.mem_budget,
        )
        self._compiled_refs.add(compiled)
        return compiled

    def _catalog_stats_for(
        self, env: Dict[str, AnyRel]
    ) -> Optional[Dict[str, planner.RelationStats]]:
        """Tracked statistics for the env relations that match a catalog
        table of the same name, layout class and key-domain extents — the
        guard that lets anonymous wrapper environments (whose names are
        program-local, e.g. the GCN's ``Edge``/``Node``) pick up catalog
        statistics without a same-named but unrelated table leaking in."""
        out: Dict[str, planner.RelationStats] = {}
        for name, rel in env.items():
            if name not in self.catalog:
                continue
            e = self.catalog.entry(name)
            if (
                type(rel) is type(e.relation)
                and rel.key_arity == len(e.stats.distinct)
                and tuple(int(x) for x in rel.extents) == e.stats.extents
            ):
                out[name] = e.stats
        return out or None

    def execute(
        self,
        program,
        env: Dict[str, AnyRel],
        seed: Optional[AnyRel] = None,
        *,
        donate: Tuple[str, ...] = (),
        stats: Optional[Dict[str, planner.RelationStats]] = None,
    ):
        """Staged execution of a program over an *anonymous* environment
        (relations passed directly rather than named in the catalog) —
        the path the relational operator layer steps through. Uses the
        session's mesh, dispatch table and memory budget, auto-threads
        committed layouts (``Lowered.compile_auto``) so repeated calls
        neither re-plan nor silently reshard, and — when an env relation
        matches a registered catalog table by name, layout class and
        extents — sources that relation's tracked statistics for the
        planner (register e.g. a GCN edge relation with ``db.put`` to get
        statistics-priced scatter plans out of the wrapper ops)."""
        if stats is None:
            stats = self._catalog_stats_for(env)
        compiled = self._compiled_for(
            program, env, seed, donate=donate, stats=stats
        )
        return compiled(env, seed)

    # -- session executable cache (serving batch buckets etc.) -------------

    def cached_executable(self, key, build: Callable[[], Any]):
        """One compiled executable per ``key`` in the session's LRU
        cache: returns the cached value (a hit), or ``build()``'s result
        after inserting it (a miss), evicting least-recently-used entries
        beyond ``max_cache_entries``. ``db.counters()["cache"]`` counts
        hits, misses and evictions — the serving front door asserts on
        them."""
        cache = self._counters["cache"]
        hit = self._exec_cache.get(key)
        if hit is not None:
            self._exec_cache.move_to_end(key)
            cache["hits"] += 1
            return hit
        cache["misses"] += 1
        val = build()
        self._exec_cache[key] = val
        if self.max_cache_entries is not None:
            while len(self._exec_cache) > self.max_cache_entries:
                self._exec_cache.popitem(last=False)
                cache["evictions"] += 1
        return val


# ---------------------------------------------------------------------------
# QueryHandle: a differentiable, compiled query over the catalog
# ---------------------------------------------------------------------------


def _base_names(roots) -> Tuple[str, ...]:
    """Base-relation names a set of graph roots read from the catalog:
    TableScan names plus Const refs, excluding the engine-internal
    ``__seed`` / ``__fwd_*`` references."""
    names = set()
    for root in roots:
        for node in root.topo():
            if isinstance(node, fra.TableScan):
                names.add(node.name)
            elif isinstance(node, fra.Const) and not node.ref.startswith("__"):
                names.add(node.ref)
    return tuple(sorted(names))


class QueryHandle:
    """A differentiable query bound to a session's catalog.

    ``forward()`` runs the query; ``grad(wrt=...)`` runs the
    RA-autodiff-generated gradient queries; ``step(donate=...)`` is the
    training hot path — forward + all gradients in one compiled
    executable, optionally donating parameter buffers. All three source
    relations, statistics, mesh, dispatch table and committed layouts
    from the catalog, and cache their compiled executables across calls
    (``trace_count`` stays flat; plans are bit-stable under
    ``compile_auto``)."""

    def __init__(
        self,
        db: Database,
        query: fra.Query,
        *,
        default_wrt: Optional[Tuple[str, ...]] = None,
    ):
        self.db = db
        self.query = query
        #: default gradient targets when grad/step get no ``wrt``.
        self.default_wrt = default_wrt
        self._grad_progs: Dict[Tuple[str, ...], GradientProgram] = {}
        self._full_prog: Optional[GradientProgram] = None
        #: the most recently used Compiled (plans/placements/resolutions).
        self.last: Optional[Any] = None

    def check(self, *, wrt: Optional[Sequence[str]] = None):
        """``db.check`` on this handle's query (see ``Database.check``);
        ``wrt`` defaults to the handle's gradient targets, so partial-RJP
        warnings cover exactly the inputs ``grad``/``step`` would
        differentiate."""
        if wrt is None:
            wrt = self.default_wrt or self.query.inputs
        return self.db.check(self.query, wrt=tuple(wrt))

    # -- environments off the catalog -------------------------------------

    def _env(self, names: Sequence[str]) -> Dict[str, AnyRel]:
        return {n: self.db.get(n) for n in names}

    def _record(self, compiled, names: Sequence[str]) -> None:
        self.last = compiled
        if compiled.mesh is not None:
            for n in names:
                spec = compiled.planned_spec(n)
                if spec is not None and n in self.db.catalog:
                    self.db.catalog.record_layout(n, spec)

    # -- the three entry points --------------------------------------------

    def forward(self):
        """Execute the (forward) query; returns its output relation."""
        names = _base_names([self.query.root])
        env = self._env(names)
        compiled = self.db._compiled_for(
            self.query, env, stats=self.db.catalog.snapshot(names)
        )
        self._record(compiled, names)
        return compiled(env)

    def _program(self, wrt: Optional[Sequence[str]]) -> GradientProgram:
        if wrt is None:
            wrt = self.default_wrt
        if self._full_prog is None:
            if not self.query.inputs:
                raise ValueError(
                    "query has no differentiable inputs; pass wrt= to "
                    "db.sql(...) / declare inputs on the fra.Query"
                )
            self._full_prog = ra_autodiff(self.query)
        if wrt is None:
            return self._full_prog
        wrt = tuple(wrt)
        missing = set(wrt) - set(self._full_prog.grads)
        if missing:
            raise ValueError(
                f"no gradient for {sorted(missing)}; differentiable inputs "
                f"are {sorted(self._full_prog.grads)}"
            )
        prog = self._grad_progs.get(wrt)
        if prog is None:
            prog = GradientProgram(
                self._full_prog.forward,
                {n: self._full_prog.grads[n] for n in wrt},
                wrt,
            )
            self._grad_progs[wrt] = prog
        return prog

    def _seed_rel(self, seed) -> Optional[AnyRel]:
        if seed is None or isinstance(seed, (DenseRelation, CooRelation)):
            return seed
        return DenseRelation(jnp.asarray(seed), self.query.root.key_arity)

    @spanned(SESSION_STEP)
    def _run_grad(
        self,
        wrt: Optional[Sequence[str]],
        seed,
        donate: Tuple[str, ...],
    ):
        prog = self._program(wrt)
        names = _base_names(
            [prog.forward.root, *prog.grads.values()]
        )
        env = self._env(names)
        bad = set(donate) - set(env)
        if bad:
            raise ValueError(
                f"cannot donate {sorted(bad)}: not relations of this query "
                f"(env: {sorted(env)})"
            )
        seed_rel = self._seed_rel(seed)
        compiled = self.db._compiled_for(
            prog, env, seed_rel,
            donate=tuple(sorted(donate)),
            stats=self.db.catalog.snapshot(names),
        )
        self._record(compiled, names)
        out, grads = compiled(env, seed_rel)
        for n in donate:
            self.db.catalog.entry(n).donated = True
        return out, grads

    def grad(self, *, wrt: Optional[Sequence[str]] = None, seed=None):
        """Gradients of the query output w.r.t. the (``wrt``-selected)
        differentiable inputs: ``{name: relation}``. ``seed`` is the
        output cotangent (default: ones — requires a scalar-loss
        output); arrays are wrapped at the output's key arity."""
        _, grads = self._run_grad(wrt, seed, ())
        return grads

    def step(
        self,
        *,
        wrt: Optional[Sequence[str]] = None,
        seed=None,
        donate: Tuple[str, ...] = (),
    ):
        """One compiled training step: ``(output, gradients)`` from a
        single jitted executable. ``donate`` names catalog relations
        whose buffers the step may reuse (parameters on the hot path) —
        a donated relation must be re-``put`` before its next read, and
        the catalog enforces that."""
        return self._run_grad(wrt, seed, tuple(donate))

    # -- introspection -----------------------------------------------------

    def plan(
        self,
        *,
        geometry: Optional[planner.MeshGeometry] = None,
        n_devices: Optional[int] = None,
        use_stats: bool = True,
    ) -> Dict[int, planner.JoinPlan]:
        """Planning-only inspection: the physical ``JoinPlan`` per join
        the optimizer would choose for this query on a mesh of the given
        geometry, sourced from the catalog (set ``use_stats=False`` for
        the stats-less heuristic baseline — comparing the two shows what
        the tracked statistics changed)."""
        names = _base_names([self.query.root])
        env = self._env(names)
        if n_devices is None:
            n_devices = geometry.model_size if geometry is not None else 1
        return planner.plan_query(
            self.query,
            env,
            n_devices,
            mem_budget=self.db.mem_budget,
            geometry=geometry,
            stats=self.db.catalog.snapshot(names) if use_stats else None,
        )

    @property
    def plans(self) -> Dict[int, planner.JoinPlan]:
        """The physical plans of the most recent compiled executable."""
        if self.last is None:
            raise ValueError("no compiled step yet: call forward/grad/step")
        return self.last.plans

    @property
    def placements(self):
        """Per-relation {"data": dim, "model": dim} placements of the
        most recent compiled executable."""
        if self.last is None:
            raise ValueError("no compiled step yet: call forward/grad/step")
        return self.last.placements

    @property
    def resolutions(self) -> Dict[str, str]:
        """Kernel-dispatch decisions of the most recent executable."""
        if self.last is None:
            raise ValueError("no compiled step yet: call forward/grad/step")
        return self.last.resolutions
