"""Kernel functions for the functional RA, with derivative registry, plus
the physical-kernel **dispatch registry** the chunked compiler routes hot
operators through.

The paper parameterizes RA operations with scalar kernel functions and, in
the chunked "tensor-relational" extension (Appendix A), with tensor kernels
(MatMul/MatAdd/...). RJP construction needs, for every kernel, its
derivative in VJP form:

  unary   ⊙ : V -> V          vjp(g, x)        =  (∂⊙(x)/∂x)ᵀ · g
  binary  ⊗ : V x V -> V      vjp_l(g, l, r)   =  (∂⊗/∂l)ᵀ · g
                              vjp_r(g, l, r)   =  (∂⊗/∂r)ᵀ · g
  agg     ⊕ : V x V -> V      commutative+associative; for ⊕ = add the
                              derivative is the identity map on g.

Kernels are looked up by name so query graphs stay picklable/hashable and
the compiler can pattern-match (e.g. ⊗ ∈ {mul, matmul} + ⊕ = add → einsum).
Per Appendix A, derivatives of *chunk* kernels may be produced by
conventional auto-diff (JAX) — that is where ``jax.grad``/``jax.vjp`` is
allowed; the relational layer above never calls it.

Separately from the *logical* kernels above, this module owns the
**dispatch registry** (``register_impl`` / ``resolve_impl`` /
``DispatchTable``): the mapping from the compiler's hot logical ops
(``segment_sum`` — the Σ over a CooRelation; ``blocked_matmul`` — the
matmul-shaped Σ∘⋈ einsum) to physical implementations, tiered per backend
(``pallas`` on TPU, ``interpret``/``ref`` on CPU, ``jnp`` as the default).
See docs/kernels.md for the authoring guide and the registry contract.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class UnaryKernel:
    name: str
    fn: Callable
    vjp: Callable  # vjp(g, x)
    # Streamability hints for out-of-core wave planning (core/planner.py):
    #   linear           — ⊙(a + b) = ⊙(a) + ⊙(b); safe after a Σ that has
    #                      only been partially accumulated across waves
    #   zero_preserving  — ⊙(0) = 0; safe on a segment grid whose untouched
    #                      segments are still the Σ unit (owner-aligned waves)
    linear: bool = False
    zero_preserving: bool = False

    def __repr__(self) -> str:
        return f"⊙{self.name}"


@dataclass(frozen=True)
class BinKernel:
    name: str
    fn: Callable
    vjp_l: Callable  # vjp_l(g, l, r)
    vjp_r: Callable  # vjp_r(g, l, r)
    # "multiplicative" kernels admit the paper's §4 ⋈_const-elimination:
    # ∂⊗/∂l depends only on (g, r) and ∂⊗/∂r only on (g, l).
    multiplicative: bool = False
    # einsum lowering hints for the chunked compiler:
    #   elementwise  — ⊗ multiplies chunks pointwise (broadcasting)
    #   chunk_spec   — (l, r, out) einsum letters over *chunk* dims
    #                  (e.g. matmul: ('mk', 'kn', 'mn')); lowercase reserved
    #                  for chunks, uppercase for block-key axes.
    elementwise: bool = False
    chunk_spec: Optional[tuple] = None

    def __repr__(self) -> str:
        return f"⊗{self.name}"


@dataclass(frozen=True)
class AggKernel:
    name: str
    fn: Callable  # fn(a, b), commutative + associative
    # unit for reductions over an empty/masked set, as a float
    unit: float = 0.0
    # is ⊕ == +? (enables the paper's constant-grp RJP simplification and
    # einsum lowering)
    is_add: bool = True

    def __repr__(self) -> str:
        return f"⊕{self.name}"


_UNARY: Dict[str, UnaryKernel] = {}
_BIN: Dict[str, BinKernel] = {}
_AGG: Dict[str, AggKernel] = {}


def register_unary(
    name: str,
    fn: Callable,
    vjp: Optional[Callable] = None,
    linear: bool = False,
    zero_preserving: bool = False,
) -> UnaryKernel:
    if vjp is None:
        # Appendix A: chunk-kernel derivatives via conventional auto-diff.
        def vjp(g, x, _fn=fn):  # type: ignore[no-redef]
            _, pull = jax.vjp(_fn, x)
            return pull(g)[0]

    k = UnaryKernel(name, fn, vjp, linear, zero_preserving)
    _UNARY[name] = k
    return k


def register_bin(
    name: str,
    fn: Callable,
    vjp_l: Optional[Callable] = None,
    vjp_r: Optional[Callable] = None,
    multiplicative: bool = False,
    elementwise: bool = False,
    chunk_spec: Optional[tuple] = None,
) -> BinKernel:
    if vjp_l is None:
        def vjp_l(g, l, r, _fn=fn):  # type: ignore[no-redef]
            _, pull = jax.vjp(_fn, l, r)
            return pull(g)[0]

    if vjp_r is None:
        def vjp_r(g, l, r, _fn=fn):  # type: ignore[no-redef]
            _, pull = jax.vjp(_fn, l, r)
            return pull(g)[1]

    k = BinKernel(name, fn, vjp_l, vjp_r, multiplicative, elementwise, chunk_spec)
    _BIN[name] = k
    return k


def register_agg(name: str, fn: Callable, unit: float = 0.0, is_add: bool = True) -> AggKernel:
    k = AggKernel(name, fn, unit, is_add)
    _AGG[name] = k
    return k


def unary(name: str) -> UnaryKernel:
    return _UNARY[name]


def bin_kernel(name: str) -> BinKernel:
    return _BIN[name]


def agg(name: str) -> AggKernel:
    return _AGG[name]


# ---------------------------------------------------------------------------
# Standard kernels
# ---------------------------------------------------------------------------

# -- aggregation ⊕ ----------------------------------------------------------
ADD = register_agg("add", lambda a, b: a + b)           # scalars and chunks
MATADD = register_agg("matadd", lambda a, b: a + b)      # alias, paper's name
MAX = register_agg("max", jnp.maximum, unit=-jnp.inf, is_add=False)

# -- binary ⊗ ---------------------------------------------------------------
MUL = register_bin(
    "mul",
    lambda l, r: l * r,
    vjp_l=lambda g, l, r: g * r,
    vjp_r=lambda g, l, r: g * l,
    multiplicative=True,
    elementwise=True,
)

# Blocked matrix multiply over chunks. vjp_l/vjp_r are the paper's Fig. 4
# optimized RJP kernels: dL = g @ rᵀ, dR = lᵀ @ g.
MATMUL = register_bin(
    "matmul",
    lambda l, r: jnp.matmul(l, r),
    vjp_l=lambda g, l, r: jnp.matmul(g, jnp.swapaxes(r, -1, -2)),
    vjp_r=lambda g, l, r: jnp.matmul(jnp.swapaxes(l, -1, -2), g),
    multiplicative=True,
    chunk_spec=("mk", "kn", "mn"),
)

ADD2 = register_bin(
    "add2",
    lambda l, r: l + r,
    vjp_l=lambda g, l, r: g,
    vjp_r=lambda g, l, r: g,
)

SUB = register_bin(
    "sub",
    lambda l, r: l - r,
    vjp_l=lambda g, l, r: g,
    vjp_r=lambda g, l, r: -g,
)

# cross-entropy ⊗ for logistic regression (paper §2.3):
#   ⊗(yhat, y) = -y·log(yhat) + (y-1)·log(1-yhat)
XENT = register_bin(
    "xent",
    lambda yhat, y: -y * jnp.log(yhat) + (y - 1.0) * jnp.log1p(-yhat),
    vjp_l=lambda g, yhat, y: g * (-y / yhat - (y - 1.0) / (1.0 - yhat)),
    vjp_r=lambda g, yhat, y: g * (-jnp.log(yhat) + jnp.log1p(-yhat)),
)

# squared error ⊗(pred, target) = 0.5(pred-target)^2, for NNMF / KGE
SQERR = register_bin(
    "sqerr",
    lambda p, t: 0.5 * (p - t) ** 2,
    vjp_l=lambda g, p, t: g * (p - t),
    vjp_r=lambda g, p, t: g * (t - p),
)

# -- unary ⊙ ----------------------------------------------------------------
IDENT = register_unary(
    "ident", lambda x: x, vjp=lambda g, x: g, linear=True, zero_preserving=True
)
NEG = register_unary(
    "neg", lambda x: -x, vjp=lambda g, x: -g, linear=True, zero_preserving=True
)
LOGISTIC = register_unary(
    "logistic",
    jax.nn.sigmoid,
    vjp=lambda g, x: g * jax.nn.sigmoid(x) * (1.0 - jax.nn.sigmoid(x)),
)
RELU = register_unary(
    "relu", jax.nn.relu, vjp=lambda g, x: g * (x > 0), zero_preserving=True
)
EXP = register_unary("exp", jnp.exp, vjp=lambda g, x: g * jnp.exp(x))
SQUARE = register_unary(
    "square", lambda x: x * x, vjp=lambda g, x: 2.0 * g * x, zero_preserving=True
)
# Reduce a chunk to a scalar value (chunked losses). Chunk-local semantics:
# executors vmap kernels over block-key axes, so jnp.sum sees one chunk.
SUM_CHUNK = register_unary(
    "sum_chunk",
    lambda x: jnp.sum(x),
    vjp=lambda g, x: g * jnp.ones_like(x),
    linear=True,
    zero_preserving=True,
)
SCALE: Dict[float, UnaryKernel] = {}


def scale_kernel(c: float) -> UnaryKernel:
    """⊙(x) = c·x — memoized per constant."""
    key = float(c)
    if key not in SCALE:
        SCALE[key] = register_unary(
            f"scale[{key}]",
            lambda x, _c=key: _c * x,
            vjp=lambda g, x, _c=key: _c * g,
            linear=True,
            zero_preserving=True,
        )
    return SCALE[key]


# ---------------------------------------------------------------------------
# Kernel dispatch registry: (logical op, backend, predicate) → implementation
#
# The chunked compiler (compiler.py) has two hardware hot-spots:
#
#   segment_sum     Σ over a CooRelation — fn(msg2d, seg, num_segments),
#                   msg2d: (E, D) float, seg: (E,) int32 (out-of-range ids
#                   are dropped), returns (num_segments, D).
#   blocked_matmul  the matmul-shaped Σ∘⋈ einsum — fn(x2d, y2d) → x @ y.
#   gather_join     the COO gather join (edge ⋈ node) and the restricted-
#                   join sparse-gradient gather — fn(table2d, rows),
#                   table2d: (N, D), rows: (E,) int32; out-of-range /
#                   negative ids (COO nnz padding) yield zero rows;
#                   returns (E, D).
#
# Instead of calling jax.ops.segment_sum / jnp.einsum directly, the
# compiler resolves each site against this registry at lowering time. A
# resolved choice is pinned by the DispatchTable the engine carries, so
# kernel selection is part of the lowering signature and hence of the jit
# cache key (core/engine.py). Tiers, from most to least specialized:
#
#   pallas     the hand-tiled TPU kernels (kernels/segsum, kernels/matmul)
#   interpret  the same Pallas kernels in interpreter mode — CPU
#              correctness tier for kernel logic, slow by construction
#   ref        the kernels' pure-jnp oracles (kernels/*/ref.py)
#   jnp        the compiler's original jnp lowering (einsum / segment_sum);
#              always registered, always applicable — the default tier
# ---------------------------------------------------------------------------

#: logical ops the compiler routes through the registry.
DISPATCH_OPS: Tuple[str, ...] = ("segment_sum", "blocked_matmul", "gather_join")

#: known tiers, in decreasing specialization order. ``sanitizer`` is the
#: instrumented cross-check tier: it replays the kernel's declared grid
#: model with out-of-bounds / write-race / uninitialized-accumulator
#: instrumentation (raising SanitizerError) and computes through the ref
#: oracle — never part of a default table, selected explicitly via
#: ``make_table("sanitizer")`` by CI and debugging sessions.
DISPATCH_TIERS: Tuple[str, ...] = ("pallas", "interpret", "sanitizer", "ref", "jnp")


class KernelDispatchError(LookupError):
    """No registered implementation matched (op, backend, predicate)."""


@dataclass(frozen=True)
class KernelImpl:
    """One registry entry.

    ``predicate(info)`` sees a dict of shape/dtype facts for the call site
    (segment_sum: nnz/dim/num_segments/dtype; blocked_matmul: m/k/n/dtype)
    and must be a pure function of it — resolution happens at lowering
    time and is replayed on retrace, so a flappy predicate would desync
    the lowering from its cache key.
    """

    op: str
    tier: str
    fn: Callable
    backends: Tuple[str, ...] = ()   # () = any jax platform
    priority: int = 0                # higher wins within a tier
    predicate: Optional[Callable] = None

    def __repr__(self) -> str:
        plats = ",".join(self.backends) or "any"
        return f"<{self.op}:{self.tier}@{plats}>"


_IMPLS: Dict[Tuple[str, str], List[KernelImpl]] = {}


def register_impl(
    op: str,
    tier: str,
    fn: Callable,
    *,
    backends: Tuple[str, ...] = (),
    priority: int = 0,
    predicate: Optional[Callable] = None,
) -> KernelImpl:
    """Register a physical implementation for a logical op under a tier.

    Entries within one (op, tier) bucket are tried in decreasing
    ``priority``; the first whose backend list admits the current platform
    and whose predicate accepts the site's shape/dtype info wins.
    """
    if tier not in DISPATCH_TIERS:
        raise ValueError(f"unknown tier {tier!r}; have {DISPATCH_TIERS}")
    impl = KernelImpl(op, tier, fn, tuple(backends), priority, predicate)
    bucket = _IMPLS.setdefault((op, tier), [])
    bucket.append(impl)
    bucket.sort(key=lambda i: -i.priority)
    return impl


@dataclass(frozen=True)
class DispatchTable:
    """Immutable (hashable) tier preference per logical op, pinned to one
    backend. This is the object the engine folds into the lowering
    signature: two tables that differ in any op's tier order produce
    distinct ``Lowered`` objects and therefore distinct jitted steps."""

    backend: str
    entries: Tuple[Tuple[str, Tuple[str, ...]], ...]  # sorted by op name

    def tiers(self, op: str) -> Tuple[str, ...]:
        for name, tiers in self.entries:
            if name == op:
                return tiers
        return ("jnp",)

    def describe(self) -> str:
        body = ", ".join(
            f"{op}→{'>'.join(tiers)}" for op, tiers in self.entries
        )
        return f"[{self.backend}] {body}"


def default_table(backend: Optional[str] = None) -> DispatchTable:
    """The default tier order for a backend: Pallas kernels (predicate-
    gated, jnp fallback) on TPU; the plain jnp lowerings elsewhere —
    CPU keeps its historical behaviour unless a tier is forced."""
    backend = backend or jax.default_backend()
    tiers = ("pallas", "jnp") if backend == "tpu" else ("jnp",)
    return DispatchTable(
        backend, tuple((op, tiers) for op in sorted(DISPATCH_OPS))
    )


def table_for_mesh(table: DispatchTable, mesh) -> DispatchTable:
    """The table a step compiled against ``mesh`` runs under. XLA's SPMD
    partitioner cannot split a Pallas TPU kernel (a Mosaic call must sit
    inside a ``shard_map``), so on a mesh of more than one device the
    ``pallas`` tier leaves every op's order and the rest of it applies:
    the default TPU table becomes ``jnp`` for all three ops. The engine
    applies this once, where ``Lowered.compile`` meets the mesh."""
    if mesh is None or mesh.size <= 1:
        return table
    entries = tuple(
        (op, tuple(t for t in tiers if t != "pallas") or ("jnp",))
        for op, tiers in table.entries
    )
    return DispatchTable(table.backend, entries)


def make_table(spec=None, backend: Optional[str] = None) -> DispatchTable:
    """Normalize a dispatch request into a DispatchTable.

    ``spec`` may be: None / ``"auto"`` (backend default), an existing
    DispatchTable, a tier name applied to every op (``"ref"``), a tuple of
    tier names tried in order, or a dict ``{op: tier | (tiers...)}`` —
    unmentioned ops keep their default tiers.
    """
    requested = backend
    backend = backend or jax.default_backend()
    if isinstance(spec, DispatchTable):
        if requested is not None and spec.backend != requested:
            raise ValueError(
                f"DispatchTable is pinned to backend {spec.backend!r} and "
                f"cannot be reinterpreted for {requested!r}; rebuild it "
                "with make_table(<tier spec>, backend=...)"
            )
        return spec
    if spec is None or spec == "auto":
        return default_table(backend)

    def norm(tiers) -> Tuple[str, ...]:
        if isinstance(tiers, str):
            tiers = (tiers,)
        tiers = tuple(tiers)
        bad = [t for t in tiers if t not in DISPATCH_TIERS]
        if bad:
            raise ValueError(f"unknown tier(s) {bad}; have {DISPATCH_TIERS}")
        return tiers

    if isinstance(spec, (str, tuple, list)):
        tiers = norm(spec)
        return DispatchTable(
            backend, tuple((op, tiers) for op in sorted(DISPATCH_OPS))
        )
    if isinstance(spec, dict):
        unknown = set(spec) - set(DISPATCH_OPS)
        if unknown:
            raise ValueError(f"unknown op(s) {sorted(unknown)}; have {DISPATCH_OPS}")
        base = dict(default_table(backend).entries)
        base.update({op: norm(t) for op, t in spec.items()})
        return DispatchTable(backend, tuple(sorted(base.items())))
    raise TypeError(f"cannot build a DispatchTable from {type(spec)}")


def resolve_impl(op: str, info: Dict, table: Optional[DispatchTable] = None) -> KernelImpl:
    """Walk the table's tier order for ``op`` and return the first
    implementation whose backend and predicate admit this site."""
    table = table or default_table()
    for tier in table.tiers(op):
        for impl in _IMPLS.get((op, tier), ()):
            if impl.backends and table.backend not in impl.backends:
                continue
            if impl.predicate is not None and not impl.predicate(info):
                continue
            return impl
    raise KernelDispatchError(
        f"no implementation of {op!r} for backend {table.backend!r} under "
        f"tiers {table.tiers(op)} with site info {info}"
    )


# ---------------------------------------------------------------------------
# Kernel contracts: the statically checkable shape of a Pallas kernel
#
# Every kernel package declares a ``CONTRACT`` (a KernelContract) next to
# its registration: the dtype domain its hardware tiers accept, the f32
# accumulator it carries, the masking obligations the wrapper discharges
# (COO_PAD_KEY rows, clamp-and-mask), which dispatch ops its custom VJP
# re-enters, and — the load-bearing part — a ``grid_model`` mapping a
# dispatch site-info dict to the exact ``grid`` + BlockSpec index maps the
# kernel would launch (padding mirrored from the ops.py wrapper).
#
# ``analysis.kernelcheck`` interprets the model abstractly (every output
# block stored by exactly one program instance, all index maps in-bounds,
# accumulator initialized before use); the ``sanitizer`` dispatch tier
# interprets the same model concretely at runtime. The vocabulary lives
# here, not in analysis/, so kernel packages never import the analysis
# layer.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Interval:
    """Inclusive integer range for index-map coordinates that are only
    known as a range statically (scalar-prefetched row ids)."""

    lo: int
    hi: int

    def __repr__(self) -> str:
        return f"[{self.lo}..{self.hi}]"


#: an index-map coordinate: exact, or an inclusive range.
Coord = Union[int, Interval]


@dataclass(frozen=True)
class BlockModel:
    """One operand's BlockSpec, abstractly: the (padded) array shape the
    kernel addresses, the block shape, and the index map from grid
    coordinates to block indices (returning ``Coord`` per dim)."""

    name: str
    array_shape: Tuple[int, ...]
    block_shape: Tuple[int, ...]
    index_map: Callable[..., Tuple[Coord, ...]]

    def block_counts(self) -> Tuple[int, ...]:
        return tuple(
            -(-a // b) for a, b in zip(self.array_shape, self.block_shape)
        )


@dataclass(frozen=True)
class AccumModel:
    """A VMEM scratch accumulator carried across the ``axis`` grid
    dimension: zeroed when the axis coordinate equals ``init_at``, with
    the output block stored at the axis' last step (``store="last"``) or
    at every step (``store="every"``, the scan kernels)."""

    axis: int
    init_at: int = 0
    store: str = "last"  # "last" | "every"


@dataclass(frozen=True)
class GridModel:
    """The launch geometry of one kernel instantiation: grid extents,
    input/output block models, and the optional accumulator."""

    grid: Tuple[int, ...]
    inputs: Tuple[BlockModel, ...]
    output: BlockModel
    accumulator: Optional[AccumModel] = None


@dataclass(frozen=True)
class VjpPair:
    """One dispatch op the kernel's custom VJP re-enters at the forward's
    tier; ``info_map`` translates the forward site info into the backward
    site's info dict."""

    op: str
    info_map: Callable[[Dict], Dict]


@dataclass(frozen=True)
class KernelContract:
    """The statically checkable contract of one kernel package.

    ``dtypes`` is the domain of the hardware (pallas/interpret) tiers —
    ``"floating"`` or ``"any"``; ``accum_dtype`` names the accumulator
    dtype the grid model's AccumModel carries; ``masking`` lists the
    pad-and-mask obligations the ops.py wrapper discharges (prose,
    rendered in docs/kernels.md); ``vjp`` describes the backward;
    ``vjp_pairs`` are the dispatch ops it re-enters in-tier;
    ``grid_model(info, **concrete)`` builds the GridModel for a site
    (``None`` when the site degenerates, e.g. an empty gather) —
    ``concrete`` may carry runtime operands (the sanitizer passes actual
    row ids) to sharpen Interval coordinates into exact ones.
    """

    op: str
    dtypes: str
    accum_dtype: str
    masking: Tuple[str, ...]
    vjp: str
    vjp_pairs: Tuple[VjpPair, ...]
    grid_model: Callable[..., Optional[GridModel]]


#: kernel package module per contract-carrying op. ``ssm_scan`` carries a
#: contract but no registry entries (the models layer calls it directly).
_CONTRACT_MODULES: Dict[str, str] = {
    "segment_sum": "repro.kernels.segsum.ops",
    "blocked_matmul": "repro.kernels.matmul.ops",
    "gather_join": "repro.kernels.gather.ops",
    "ssm_scan": "repro.kernels.ssm_scan.ops",
}


def contract_ops() -> Tuple[str, ...]:
    """Ops with a declared KernelContract (dispatch ops + ssm_scan)."""
    return tuple(_CONTRACT_MODULES)


def kernel_contract(op: str) -> KernelContract:
    """The ``CONTRACT`` declared in ``op``'s kernel package (lazy import,
    matching the lazy impl wrappers below)."""
    import importlib

    mod = _CONTRACT_MODULES.get(op)
    if mod is None:
        raise KeyError(f"no kernel contract for op {op!r}; have {contract_ops()}")
    return importlib.import_module(mod).CONTRACT


# -- grid-model interpretation ----------------------------------------------
# Shared by the static certifier (analysis/kernelcheck.py wraps violations
# into node-path Diagnostics) and the sanitizer tier (raises
# SanitizerError). Index maps are affine in the grid coordinates (the only
# shape Pallas BlockSpecs take in this repo), which is what makes corner
# sampling sound for grids too large to enumerate.

#: grids at most this large are enumerated exhaustively (exact coverage /
#: race counts); larger grids are corner-sampled (bounds + race only).
GRID_ENUM_CAP: int = 32768


class SanitizerError(RuntimeError):
    """A sanitizer-tier instrumentation check failed. ``kind`` is the
    violation code, matching the static certifier's diagnostic codes."""

    def __init__(self, kind: str, detail: str):
        super().__init__(f"[{kind}] {detail}")
        self.kind = kind
        self.detail = detail


def _grid_coords(grid: Tuple[int, ...], cap: int) -> Tuple[List[Tuple[int, ...]], bool]:
    import itertools

    total = 1
    for s in grid:
        total *= s
    if total <= cap:
        pts = list(itertools.product(*(range(s) for s in grid)))
        return pts, True
    corners = [
        sorted({p for p in (0, 1, s - 2, s - 1) if 0 <= p < s}) for s in grid
    ]
    return list(itertools.product(*corners)), False


def _map_axis_deps(index_map: Callable, grid: Tuple[int, ...]) -> Tuple[int, ...]:
    """Grid axes the index map depends on, by probing unit moves from the
    origin (sound for affine maps)."""
    base = index_map(*(0,) * len(grid))
    deps = []
    for ax, size in enumerate(grid):
        if size <= 1:
            continue
        probe = [0] * len(grid)
        probe[ax] = size - 1
        if index_map(*probe) != base:
            deps.append(ax)
    return tuple(deps)


def _coord_range(v: Coord) -> Tuple[int, int]:
    if isinstance(v, Interval):
        return v.lo, v.hi
    return int(v), int(v)


def simulate_grid(
    model: GridModel, cap: int = GRID_ENUM_CAP
) -> List[Tuple[str, str]]:
    """Interpret a kernel's grid model and return ``(kind, detail)``
    violations (empty = sound). Kinds: ``grid-oob-index`` (an input or
    output block index leaves the padded array), ``grid-race`` (an output
    block stored by more than one program instance), ``grid-uncovered``
    (an output block never stored; exhaustive enumeration only),
    ``grid-reduction-order`` (revisit axes not innermost, so a VMEM
    accumulator would be clobbered between partial sums), and
    ``uninit-accumulator`` (accumulated before its zeroing step)."""
    viols: List[Tuple[str, str]] = []
    grid = model.grid
    if any(s <= 0 for s in grid):
        return viols
    coords, exhaustive = _grid_coords(grid, cap)
    acc = model.accumulator

    # revisit axes (grid axes the output map ignores — the reduction /
    # sweep axes) must be the innermost suffix: the TPU grid executes
    # sequentially with the last axis fastest, so only a trailing sweep
    # keeps one output block's partial sums adjacent in time.
    out_deps = set(_map_axis_deps(model.output.index_map, grid))
    revisit = [ax for ax in range(len(grid)) if ax not in out_deps and grid[ax] > 1]
    if revisit != list(range(len(grid) - len(revisit), len(grid))):
        viols.append((
            "grid-reduction-order",
            f"revisit axes {tuple(revisit)} of grid {grid} are not the "
            f"innermost suffix (output map depends on axes {tuple(sorted(out_deps))})",
        ))
    if acc is not None:
        if acc.init_at != 0:
            viols.append((
                "uninit-accumulator",
                f"accumulator on grid axis {acc.axis} is zeroed at step "
                f"{acc.init_at}, so steps 0..{acc.init_at - 1} accumulate "
                "into uninitialized VMEM",
            ))
        if not 0 <= acc.axis < len(grid):
            viols.append((
                "uninit-accumulator",
                f"accumulator axis {acc.axis} outside grid {grid}",
            ))
            acc = None

    oob_seen = set()
    stores: Dict[Tuple[int, ...], int] = {}
    out_counts = model.output.block_counts()
    for coord in coords:
        for bm in model.inputs + (model.output,):
            idx = bm.index_map(*coord)
            counts = bm.block_counts()
            if len(idx) != len(counts):
                if bm.name not in oob_seen:
                    oob_seen.add(bm.name)
                    viols.append((
                        "grid-oob-index",
                        f"{bm.name}: index map arity {len(idx)} != "
                        f"array rank {len(counts)}",
                    ))
                continue
            for d, (v, n) in enumerate(zip(idx, counts)):
                lo, hi = _coord_range(v)
                if lo < 0 or hi >= n:
                    key = (bm.name, d)
                    if key not in oob_seen:
                        oob_seen.add(key)
                        viols.append((
                            "grid-oob-index",
                            f"{bm.name} dim {d}: block index {v} at grid "
                            f"point {coord} outside [0, {n}) "
                            f"(array {bm.array_shape}, block {bm.block_shape})",
                        ))
        if acc is None or acc.store == "every":
            stored = True
        else:
            stored = coord[acc.axis] == grid[acc.axis] - 1
        if stored:
            oidx = model.output.index_map(*coord)
            if any(isinstance(v, Interval) for v in oidx):
                viols.append((
                    "grid-race",
                    f"output block index {oidx} at grid point {coord} is "
                    "not statically exact — cannot prove single-writer",
                ))
                continue
            oidx = tuple(int(v) for v in oidx)
            stores[oidx] = stores.get(oidx, 0) + 1

    races = sorted(idx for idx, c in stores.items() if c > 1)
    if races:
        viols.append((
            "grid-race",
            f"{len(races)} output block(s) stored by more than one program "
            f"instance, e.g. block {races[0]} stored {stores[races[0]]}x",
        ))
    if exhaustive:
        import itertools

        missing = [
            idx
            for idx in itertools.product(*(range(n) for n in out_counts))
            if idx not in stores
        ]
        if missing:
            viols.append((
                "grid-uncovered",
                f"{len(missing)} output block(s) never stored, e.g. "
                f"block {missing[0]} of {out_counts}",
            ))
    return viols


# -- dispatch-site resolution log --------------------------------------------


@dataclass(frozen=True)
class SiteRecord:
    """One dispatch decision with enough context to replay it: the
    ``op[site]`` key, the site-info dict (frozen as sorted items), and
    the tier that resolved. ``analysis.kernelcheck`` re-runs
    ``resolve_impl`` on the snapshot and flags any drift — the checked
    form of the flappy-predicate hazard on KernelImpl."""

    key: str
    op: str
    site: str
    tier: str
    info: Tuple[Tuple[str, object], ...]

    def info_dict(self) -> Dict:
        return dict(self.info)


class ResolutionLog(Dict[str, str]):
    """The ``op[site] → tier`` dict the engine exposes as
    ``Compiled.resolutions``, plus per-site SiteRecords for replay."""

    def __init__(self) -> None:
        super().__init__()
        self.sites: List[SiteRecord] = []

    def record(self, key: str, op: str, site: str, tier: str, info: Dict) -> None:
        self.sites.append(
            SiteRecord(key, op, site, tier, tuple(sorted(info.items())))
        )


# -- registered implementations ---------------------------------------------
# The pallas/interpret/ref fns import the kernel packages lazily so that
# importing repro.core stays cheap on machines that never leave the jnp
# tier.


def _is_float(info: Dict) -> bool:
    return jnp.issubdtype(jnp.dtype(info["dtype"]), jnp.floating)


def _segsum_jnp(msg: jnp.ndarray, seg: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    return jax.ops.segment_sum(msg, seg, num_segments=num_segments)


def _segsum_ref(msg: jnp.ndarray, seg: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    from repro.kernels.segsum.ref import segment_sum_ref

    return segment_sum_ref(msg, seg, num_segments)


def _segsum_pallas(msg: jnp.ndarray, seg: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    from repro.kernels.segsum.ops import segment_sum

    return segment_sum(msg, seg, num_segments, interpret=False)


def _segsum_interpret(msg: jnp.ndarray, seg: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    from repro.kernels.segsum.ops import segment_sum

    return segment_sum(msg, seg, num_segments, interpret=True)


def _matmul_jnp(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(x, y)


def _matmul_ref(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels.matmul.ref import matmul_ref

    return matmul_ref(x, y)


def _matmul_pallas(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels.matmul.ops import blocked_matmul

    return blocked_matmul(x, y, interpret=False)


def _matmul_interpret(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels.matmul.ops import blocked_matmul

    return blocked_matmul(x, y, interpret=True)


def _gather_jnp(table: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    # the default lowering IS the masked-gather oracle (one definition of
    # the COO pad-and-mask contract: out-of-range / negative ids gather
    # zero rows — see kernels/gather/ref.py)
    from repro.kernels.gather.ref import gather_rows_ref

    return gather_rows_ref(table, rows)


def _gather_ref(table: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels.gather.ref import gather_rows_ref

    return gather_rows_ref(table, rows)


def _gather_pallas(table: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels.gather.ops import gather_rows

    return gather_rows(table, rows, interpret=False)


def _gather_interpret(table: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels.gather.ops import gather_rows

    return gather_rows(table, rows, interpret=True)


# -- sanitizer tier ----------------------------------------------------------
# Instrumented cross-check impls: on concrete (eager) inputs they replay
# the contract's grid model with out-of-bounds / write-race /
# uninitialized-accumulator instrumentation (raising SanitizerError with
# the same violation codes the static certifier reports) and compute the
# result through the ref oracle; under tracing (eval_shape / jit) the
# checks cannot observe values and the impl degrades to the plain oracle.


def _is_concrete(*xs: Any) -> bool:
    return not any(isinstance(x, jax.core.Tracer) for x in xs)


def _sanitize_site(op: str, info: Dict, **concrete: Any) -> None:
    contract = kernel_contract(op)
    if contract.dtypes == "floating" and not _is_float(info):
        raise SanitizerError(
            "dtype-domain",
            f"{op}: dtype {jnp.dtype(info['dtype'])} outside the "
            f"contract's floating domain at site {info}",
        )
    model = contract.grid_model(info, **concrete)
    if model is None:
        return
    viols = simulate_grid(model)
    if viols:
        kind, detail = viols[0]
        raise SanitizerError(kind, f"{op}: {detail} (site {info})")


def _segsum_sanitizer(msg: jnp.ndarray, seg: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    from repro.kernels.segsum.ref import segment_sum_ref

    if _is_concrete(msg, seg):
        info = {
            "nnz": msg.shape[0], "dim": msg.shape[1],
            "num_segments": num_segments, "dtype": msg.dtype,
        }
        _sanitize_site("segment_sum", info)
    return segment_sum_ref(msg, seg, num_segments)


def _matmul_sanitizer(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    from repro.kernels.matmul.ref import matmul_ref

    if _is_concrete(x, y):
        info = {
            "m": x.shape[0], "k": x.shape[1], "n": y.shape[1],
            "dtype": jnp.result_type(x, y),
        }
        _sanitize_site("blocked_matmul", info)
    return matmul_ref(x, y)


def _gather_sanitizer(table: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    import numpy as np

    from repro.kernels.gather.ref import gather_rows_ref

    if _is_concrete(table, rows):
        info = {
            "rows": rows.shape[0], "num_rows": table.shape[0],
            "dim": table.shape[1], "dtype": table.dtype,
        }
        # concrete row ids sharpen the scalar-prefetch Interval into the
        # exact per-step indices the DMA pipeline would issue
        _sanitize_site("gather_join", info, rows=np.asarray(rows))
    return gather_rows_ref(table, rows)


# The hardware tiers require float inputs (the Pallas kernels accumulate in
# f32 and store the input dtype); the ref oracles accept anything their jnp
# twins accept; the jnp tier is the unconditional fallback.
register_impl(
    "segment_sum", "pallas", _segsum_pallas, backends=("tpu",), predicate=_is_float
)
register_impl("segment_sum", "interpret", _segsum_interpret, predicate=_is_float)
register_impl("segment_sum", "sanitizer", _segsum_sanitizer, predicate=_is_float)
register_impl("segment_sum", "ref", _segsum_ref)
register_impl("segment_sum", "jnp", _segsum_jnp)

register_impl(
    "blocked_matmul", "pallas", _matmul_pallas, backends=("tpu",), predicate=_is_float
)
register_impl("blocked_matmul", "interpret", _matmul_interpret, predicate=_is_float)
register_impl("blocked_matmul", "sanitizer", _matmul_sanitizer, predicate=_is_float)
register_impl("blocked_matmul", "ref", _matmul_ref)
register_impl("blocked_matmul", "jnp", _matmul_jnp)

register_impl(
    "gather_join", "pallas", _gather_pallas, backends=("tpu",), predicate=_is_float
)
register_impl("gather_join", "interpret", _gather_interpret, predicate=_is_float)
register_impl("gather_join", "sanitizer", _gather_sanitizer, predicate=_is_float)
register_impl("gather_join", "ref", _gather_ref)
register_impl("gather_join", "jnp", _gather_jnp)
