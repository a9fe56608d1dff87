"""COO nnz-dimension sharding: the planner's scatter-vs-replicate
decision (with the owner-partition edge-cut estimate and the
committed-layout rechunk fold), the owner-partitioned relation layout,
the gather_join dispatch op, the zero-nnz Σ guard, pad-and-mask for
non-divisible nnz, reshard accounting — and, under the tier1-spmd lane's
8 virtual devices, the acceptance path: a GCN grad step over an
nnz-sharded edge relation on the 4×2 host mesh matches the single-device
oracle to 1e-5."""

import pathlib
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fra
from repro.core.autodiff import ra_autodiff
from repro.core.engine import (
    RAEngine,
    ReshardWarning,
    ShardFallbackWarning,
    _committed_layouts,
)
from repro.core.kernels import ADD, MATMUL, MUL, SQUARE, SUM_CHUNK
from repro.core.keys import (
    EMPTY_KEY,
    TRUE,
    L,
    R,
    eq_pred,
    identity_key,
    jproj,
    project_key,
)
from repro.core.planner import (
    EDGE_CUT_LOCAL,
    MeshGeometry,
    input_pspecs,
    plan_join,
    plan_query,
)
from repro.core.relation import (
    COO_PAD_KEY,
    CooRelation,
    DenseRelation,
    owner_partition,
    pad_coo_nnz,
)
from repro.launch.mesh import make_host_mesh

requires8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (tier1-spmd lane: "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

GEO = MeshGeometry("model", 2, ("data",), 4)


def gcn_query(edge_input: bool = True):
    join = fra.Join(
        eq_pred((0, 0)), jproj(L(1)), MUL,
        fra.scan("Edge", 2), fra.scan("Node", 1),
    )
    inputs = ("Edge", "Node") if edge_input else ("Node",)
    return fra.Query(fra.Agg(identity_key(1), ADD, join), inputs=inputs)


def gcn_grad_prog():
    q = gcn_query()
    sq = fra.Select(TRUE, identity_key(1), SQUARE, q.root)
    loss = fra.Agg(
        EMPTY_KEY, ADD, fra.Select(TRUE, identity_key(1), SUM_CHUNK, sq)
    )
    return ra_autodiff(fra.Query(loss, inputs=("Edge", "Node")))


def gcn_env(rng, n, nnz, d, *, shards=None):
    src = rng.integers(0, n, size=nnz)
    dst = rng.integers(0, n, size=nnz)
    # weights scaled by 1/sqrt(mean degree) keep gradient magnitudes O(1),
    # so the atol-1e-5 oracle checks measure agreement, not summation scale
    w = rng.normal(size=nnz) / np.sqrt(max(nnz / n, 1.0))
    edge = CooRelation(
        jnp.asarray(np.stack([src, dst], 1), jnp.int32),
        jnp.asarray(w, jnp.float32),
        (n, n),
    )
    if shards is not None:
        edge = owner_partition(edge, shards, dim=1)
    return {
        "Edge": edge,
        "Node": DenseRelation(
            jnp.asarray(rng.normal(size=(n, d)), jnp.float32), 1
        ),
    }


def _coo(nnz, n=64, chunk=()):
    return CooRelation(
        jnp.zeros((nnz, 2), jnp.int32),
        jnp.zeros((nnz,) + chunk, jnp.float32),
        (n, n),
    )


# ---------------------------------------------------------------------------
# Planner: scatter-vs-replicate crossover, edge cut, rechunk fold
# ---------------------------------------------------------------------------


def test_planner_shards_nnz_when_edges_dominate():
    """A big edge list against small node features: sharding the nnz rows
    (psum_scatter of the segment grid) beats replicating the COO."""
    env = {"Edge": _coo(100_000), "Node": DenseRelation(jnp.zeros((64, 8), jnp.float32), 1)}
    q = gcn_query()
    plans = plan_query(q, env, 2, geometry=GEO)
    (plan,) = plans.values()
    assert plan.coo_sides == (True, False)
    assert plan.data_kind == "data:shard_nnz_left"
    assert plan.nnz_sharded("left") and not plan.nnz_sharded("right")
    assert plan.needs_data_psum          # the planned scatter collective
    assert plan.costs["data:shard_nnz_left"] < plan.costs["data:replicate"]
    specs = input_pspecs(q, plans)
    assert specs["Edge"] == P("data")    # nnz rows on the data axes
    # a COO side never carries the model axis / a key-dim spec
    assert plan.left_shard_dim is None


def test_planner_replicates_small_edge_lists():
    """The crossover: few edges against a big node grid — replicating the
    COO is cheaper than paying the Σ's scatter."""
    env = {"Edge": _coo(16, n=2048), "Node": DenseRelation(jnp.zeros((2048, 64), jnp.float32), 1)}
    q = gcn_query()
    plans = plan_query(q, env, 2, geometry=GEO)
    (plan,) = plans.values()
    assert plan.data_kind == "data:replicate"
    assert input_pspecs(q, plans)["Edge"] == P()


def test_coo_side_is_never_key_sharded():
    """nnz rows are not key-sharded: when both sides bust the memory
    budget (the copartition trigger) only the *dense* side co-partitions
    on the contraction key — the COO side's shard dim stays None and its
    nnz rows still land on the data axes."""
    env = {"Edge": _coo(100_000), "Node": DenseRelation(jnp.zeros((64, 8), jnp.float32), 1)}
    q = gcn_query()
    plans = plan_query(q, env, 2, mem_budget=1.0, geometry=GEO)
    (plan,) = plans.values()
    assert plan.kind == "copartition"        # the memory-feasible 1-D plan
    assert plan.left_shard_dim is None       # COO side: no key dims
    assert plan.right_shard_dim == 0         # dense side: contraction key
    assert plan.data_kind == "data:shard_nnz_left"
    assert input_pspecs(q, plans)["Edge"] == P("data")


def test_owner_partition_discounts_the_scatter():
    """An edge relation owner-partitioned on the Σ's segment key (dst)
    prices the scatter at the EDGE_CUT_LOCAL fraction."""
    q = gcn_query()
    plain = {"Edge": _coo(100_000), "Node": DenseRelation(jnp.zeros((64, 8), jnp.float32), 1)}
    part = dict(plain)
    part["Edge"] = owner_partition(plain["Edge"], GEO.data_size, dim=1)
    (p_plain,) = plan_query(q, plain, 2, geometry=GEO).values()
    (p_part,) = plan_query(q, part, 2, geometry=GEO).values()
    c_plain = p_plain.costs["data:shard_nnz_left"]
    c_part = p_part.costs["data:shard_nnz_left"]
    assert c_part < c_plain
    # the difference is exactly the (1 - EDGE_CUT_LOCAL) scatter discount
    frac_d = (GEO.data_size - 1) / GEO.data_size
    dense_bytes = 64 * 8 * 4.0
    scatter_full = dense_bytes * frac_d     # min(sum_out, dense) = dense
    np.testing.assert_allclose(
        c_plain - c_part, scatter_full * (1.0 - EDGE_CUT_LOCAL), rtol=1e-6
    )
    # partitioned on src (not the segment key): no discount
    wrong = dict(plain)
    wrong["Edge"] = owner_partition(plain["Edge"], GEO.data_size, dim=0)
    (p_wrong,) = plan_query(q, wrong, 2, geometry=GEO).values()
    np.testing.assert_allclose(
        p_wrong.costs["data:shard_nnz_left"], c_plain, rtol=1e-6
    )


def matmul_join():
    return fra.Join(
        eq_pred((1, 0)), jproj(L(0), L(1), R(1)), MATMUL,
        fra.scan("A", 2), fra.scan("B", 2),
    )


def test_committed_layout_fold_flips_the_plan():
    """The device-layout rechunk cost (ROADMAP follow-up): a side
    committed to the wrong layout charges the all-to-all, flipping a
    copartition win into a broadcast."""
    join = matmul_join()
    free = plan_join(join, 1e6, 1e6, 1e5, 16)
    assert free.kind == "copartition"
    # A is committed with the model axis on dim 0; copartition needs its
    # contraction dim 1 — the fold charges A's all-to-all
    committed = ({"model": 0, "data": None}, None)
    folded = plan_join(join, 1e6, 1e6, 1e5, 16, committed_dims=committed)
    assert folded.kind == "broadcast_left"
    frac = 15 / 16
    np.testing.assert_allclose(
        folded.costs["copartition"] - free.costs["copartition"],
        1e6 * frac,
        rtol=1e-6,
    )
    # a matching committed layout charges nothing
    aligned = plan_join(
        join, 1e6, 1e6, 1e5, 16,
        committed_dims=({"model": 1, "data": None}, None),
    )
    assert aligned.costs["copartition"] == free.costs["copartition"]


def test_plan_query_threads_committed_specs():
    q = fra.Query(
        fra.Agg(project_key(0, 2), ADD, matmul_join()), inputs=("A", "B")
    )
    env = {
        "A": jax.ShapeDtypeStruct((512, 512, 16, 16), jnp.float32),
        "B": jax.ShapeDtypeStruct((512, 512, 16, 16), jnp.float32),
    }
    free = plan_query(q, env, 16)
    folded = plan_query(
        q, env, 16, committed={"A": P("model", None), "B": P(None, "model")}
    )
    (pf,), (pc,) = free.values(), folded.values()
    assert pc.costs["copartition"] > pf.costs["copartition"]


# ---------------------------------------------------------------------------
# Relation layer: owner partition + pad-and-mask
# ---------------------------------------------------------------------------


def test_owner_partition_sorts_pads_and_records_offsets():
    keys = jnp.asarray([[0, 3], [1, 0], [2, 2], [3, 1], [4, 3]], jnp.int32)
    vals = jnp.asarray([3.0, 0.0, 2.0, 1.0, 3.5], jnp.float32)
    rel = owner_partition(CooRelation(keys, vals, (5, 4)), 4, dim=1)
    assert rel.owner_dim == 1
    assert rel.nnz == 8                     # padded 5 -> multiple of 4
    dst = np.asarray(rel.keys[:, 1])
    assert list(dst[:5]) == sorted(dst[:5])  # sorted by owner key
    assert (dst[5:] == COO_PAD_KEY).all()    # inert padding rows
    np.testing.assert_array_equal(np.asarray(rel.values[5:]), 0.0)
    assert rel.shard_offsets == (0, 2, 3, 4)
    # a shard whose rows are all padding owns no segments: it records the
    # one-past-the-end owner extent
    tiny = owner_partition(
        CooRelation(keys[:2], vals[:2], (5, 4)), 4, dim=1
    )
    assert tiny.shard_offsets == (0, 3, 4, 4)
    # aux data (layout metadata) survives the pytree roundtrip
    leaves, treedef = jax.tree_util.tree_flatten(rel)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.owner_dim == 1 and back.shard_offsets == rel.shard_offsets


@pytest.mark.parametrize("high", [70_000, 2_449_029, 2**31 - 1])
def test_owner_partition_is_a_stable_sort_of_wide_keys(high):
    """Owner keys past 16 bits sort in numpy's stable order: rows with
    equal keys keep their order (each row's value is its position)."""
    rng = np.random.default_rng(3)
    nnz = 5000
    dst = rng.integers(0, high, size=nnz)
    dst[::7] = dst[0]                        # runs of equal keys
    keys = np.stack([np.arange(nnz), dst], axis=1).astype(np.int32)
    vals = np.arange(nnz, dtype=np.float32)
    rel = owner_partition(CooRelation(keys, vals, (nnz, high)), 4, dim=1)
    order = np.argsort(dst, kind="stable")
    np.testing.assert_array_equal(np.asarray(rel.keys)[:nnz], keys[order])
    np.testing.assert_array_equal(np.asarray(rel.values)[:nnz], vals[order])


def test_pad_coo_nnz_is_numerically_inert():
    rng = np.random.default_rng(0)
    env = gcn_env(rng, n=16, nnz=30, d=4)
    padded = dict(env)
    padded["Edge"] = pad_coo_nnz(env["Edge"], 37)
    q = gcn_query()
    out = RAEngine(q).lower(env).compile()(env)
    outp = RAEngine(q).lower(padded).compile()(padded)
    np.testing.assert_allclose(
        np.asarray(outp.data), np.asarray(out.data), rtol=1e-6
    )


# ---------------------------------------------------------------------------
# gather_join dispatch + the zero-nnz Σ guard
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tier", ("jnp", "ref", "interpret"))
def test_gather_join_resolves_and_is_recorded(tier):
    rng = np.random.default_rng(1)
    env = gcn_env(rng, n=16, nnz=40, d=8)
    prog = gcn_grad_prog()
    comp = RAEngine(prog).lower(env, dispatch=tier).compile()
    gathers = [k for k in comp.resolutions if k.startswith("gather_join[")]
    assert gathers, "no gather_join site recorded"
    assert {comp.resolutions[k] for k in gathers} == {tier}


@pytest.mark.parametrize("tier", ("ref", "interpret"))
def test_gather_join_tiers_match_jnp(tier):
    """Forward + relational gradients agree across gather tiers — the
    edge gradient exercises the restricted-join gather, the node gradient
    the reversed-edge gather."""
    rng = np.random.default_rng(2)
    env = gcn_env(rng, n=16, nnz=40, d=8)
    prog = gcn_grad_prog()
    eng = RAEngine(prog)
    out_j, grads_j = eng.lower(env, dispatch="jnp").compile()(env)
    out_t, grads_t = eng.lower(env, dispatch=tier).compile()(env)
    np.testing.assert_allclose(
        np.asarray(out_t.data), np.asarray(out_j.data), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(grads_t["Node"].data),
        np.asarray(grads_j["Node"].data),
        atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(grads_t["Edge"].values),
        np.asarray(grads_j["Edge"].values),
        atol=1e-5,
    )


@pytest.mark.parametrize("tier", ("jnp", "ref", "interpret"))
def test_zero_nnz_aggregate_is_guarded_across_tiers(tier):
    """Σ over an empty CooRelation: every registered tier produces the
    same zero grid with the values' dtype — the lowering never reaches a
    tier-specific empty segment_sum."""
    env = {
        "Edge": CooRelation(
            jnp.zeros((0, 2), jnp.int32), jnp.zeros((0,), jnp.float32), (8, 8)
        ),
        "Node": DenseRelation(jnp.ones((8, 4), jnp.float32), 1),
    }
    q = gcn_query()
    comp = RAEngine(q).lower(env, dispatch=tier).compile()
    out = comp(env)
    assert isinstance(out, DenseRelation)
    assert out.data.shape == (8, 4) and out.data.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out.data), 0.0)


def test_select_over_padded_coo_keeps_pad_rows_inert():
    """A σ kernel with f(0) != 0 (exp) must not resurrect padded rows:
    they are re-masked before a full-reduce Σ can sum them."""
    from repro.core.kernels import EXP

    keys = jnp.asarray([[0, 1], [1, 2], [2, 0]], jnp.int32)
    vals = jnp.asarray([0.5, -1.0, 2.0], jnp.float32)
    edge = owner_partition(CooRelation(keys, vals, (4, 4)), 4, dim=1)
    assert edge.nnz == 4                       # one padded row
    q = fra.Query(
        fra.Agg(
            EMPTY_KEY, ADD,
            fra.Select(TRUE, identity_key(2), EXP, fra.scan("Edge", 2)),
        ),
        inputs=("Edge",),
    )
    out = RAEngine(q).lower({"Edge": edge}).compile()({"Edge": edge})
    np.testing.assert_allclose(
        float(out.data), float(np.sum(np.exp(np.asarray(vals)))), rtol=1e-6
    )


def test_zero_nnz_gradients_are_guarded():
    env = {
        "Edge": CooRelation(
            jnp.zeros((0, 2), jnp.int32), jnp.zeros((0,), jnp.float32), (8, 8)
        ),
        "Node": DenseRelation(jnp.ones((8, 4), jnp.float32), 1),
    }
    prog = gcn_grad_prog()
    out, grads = RAEngine(prog).lower(env).compile()(env)
    np.testing.assert_array_equal(np.asarray(out.data), 0.0)
    assert grads["Edge"].values.shape == (0,)
    np.testing.assert_array_equal(np.asarray(grads["Node"].data), 0.0)


# ---------------------------------------------------------------------------
# SPMD acceptance: the 4×2 host mesh (tier1-spmd lane)
# ---------------------------------------------------------------------------


@pytest.mark.spmd
@requires8
def test_gcn_grad_step_nnz_sharded_matches_oracle():
    """Acceptance: on the 4×2 (data × model) host mesh the compiled GCN
    grad step shards the edge relation's nnz rows over "data"
    (Compiled.placements reports it), routes the gather join through the
    dispatch registry, emits the Σ's scatter collective, and matches the
    single-device oracle to 1e-5."""
    mesh = make_host_mesh(model=2)
    rng = np.random.default_rng(3)
    env = gcn_env(rng, n=64, nnz=8192, d=8, shards=4)
    prog = gcn_grad_prog()
    eng = RAEngine(prog)
    low = eng.lower(env)

    comp = low.compile(mesh=mesh)
    assert comp.placements["Edge"] == {"data": 0, "model": None}
    assert any(k.startswith("gather_join[") for k in comp.resolutions)
    (plan,) = comp.plans.values()
    assert plan.data_kind == "data:shard_nnz_left"

    out_s, grads_s = comp(env)
    walks = eng.trace_count
    comp(env)                                # jit cache hit: no re-walk
    assert eng.trace_count == walks

    out_1, grads_1 = low.compile()(env)      # single-device oracle
    np.testing.assert_allclose(
        np.asarray(out_s.data), np.asarray(out_1.data), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(grads_s["Node"].data),
        np.asarray(grads_1["Node"].data),
        atol=1e-5,
        rtol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(grads_s["Edge"].values),
        np.asarray(grads_1["Edge"].values),
        atol=1e-5,
        rtol=1e-5,
    )
    # the sharded Σ-over-edges must have produced its scatter collective
    hlo = comp.lower_text()
    assert "all-reduce" in hlo or "reduce-scatter" in hlo


@pytest.mark.spmd
@requires8
def test_non_divisible_nnz_is_padded_not_replicated():
    """8191 edges on 4 data shards: the engine pads the nnz axis
    (pad-and-mask) instead of silently replicating, results still match
    the oracle, and outputs come back unpadded."""
    mesh = make_host_mesh(model=2)
    rng = np.random.default_rng(4)
    env = gcn_env(rng, n=64, nnz=8191, d=8)
    prog = gcn_grad_prog()
    low = RAEngine(prog).lower(env)
    comp = low.compile(mesh=mesh)
    assert comp.pad_nnz == {"Edge": 8192}
    assert comp.placements["Edge"] == {"data": 0, "model": None}
    out_s, grads_s = comp(env)
    assert grads_s["Edge"].values.shape == (8191,)
    out_1, grads_1 = low.compile()(env)
    np.testing.assert_allclose(
        np.asarray(out_s.data), np.asarray(out_1.data), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(grads_s["Edge"].values),
        np.asarray(grads_1["Edge"].values),
        atol=1e-5,
        rtol=1e-5,
    )


@pytest.mark.spmd
@requires8
def test_coo_pspecs_place_the_nnz_rows():
    """launch/sharding.coo_pspecs: the manual device_put layout matches
    the planner's nnz-row fold — each data-shard holds nnz/4 rows."""
    from repro.launch.sharding import coo_pspecs, to_shardings

    mesh = make_host_mesh(model=2)
    edge = _coo(8192)
    placed = jax.device_put(edge, to_shardings(coo_pspecs(edge, mesh), mesh))
    rows = {s.data.shape[0] for s in placed.values.addressable_shards}
    assert rows == {8192 // 4}
    assert {s.data.shape for s in placed.keys.addressable_shards} == {(2048, 2)}
    assert placed.extents == edge.extents


@pytest.mark.spmd
@requires8
def test_dense_fallback_emits_structured_warning():
    """A dense extent the mesh axes do not divide falls back to
    replication with a ShardFallbackWarning naming relation and extents."""
    from repro.core.kernels import LOGISTIC, XENT

    f_matmul = fra.Agg(
        project_key(0), ADD,
        fra.Join(
            eq_pred((1, 0)), jproj(L(0), L(1)), MUL,
            fra.const("Rx", 2), fra.scan("theta", 1),
        ),
    )
    f_predict = fra.Select(TRUE, identity_key(1), LOGISTIC, f_matmul)
    f_loss = fra.Agg(
        EMPTY_KEY, ADD,
        fra.Join(eq_pred((0, 0)), jproj(L(0)), XENT, f_predict, fra.const("Ry", 1)),
    )
    prog = ra_autodiff(fra.Query(f_loss, inputs=("theta",)))
    rng = np.random.default_rng(5)
    env = {
        "Rx": DenseRelation(jnp.asarray(rng.normal(size=(65, 8)), jnp.float32), 2),
        "Ry": DenseRelation(jnp.ones((65,), jnp.float32), 1),
        "theta": DenseRelation(jnp.zeros((8,), jnp.float32), 1),
    }
    mesh = make_host_mesh(model=2)
    with pytest.warns(ShardFallbackWarning) as rec:
        RAEngine(prog).lower(env).compile(mesh=mesh)
    falls = {
        r.message.relation: r.message
        for r in rec
        if isinstance(r.message, ShardFallbackWarning)
    }
    w = falls["Rx"]
    assert w.extent == 65 and w.divisor == 4


@pytest.mark.spmd
@requires8
def test_reshard_stats_count_committed_moves_and_warn_once():
    """The silent-reshard fix: committed inputs arriving in a different
    layout are counted on Compiled.counters["reshard"], warned about once per
    cache entry, and foldable into the plan via _committed_layouts."""
    mesh = make_host_mesh(model=2)
    rng = np.random.default_rng(6)
    n, m = 64, 8
    env = {
        "A": DenseRelation(jnp.asarray(rng.normal(size=(n, n, m, m)), jnp.float32), 2),
        "B": DenseRelation(jnp.asarray(rng.normal(size=(n, n, m, m)), jnp.float32), 2),
    }
    q = fra.Query(
        fra.Agg(project_key(0, 2), ADD, matmul_join()), inputs=("A", "B")
    )
    low = RAEngine(q).lower(env)
    comp = low.compile(mesh=mesh)
    # commit A against the planned layout
    wrong = NamedSharding(mesh, P(None, None, "model", None))
    env_wrong = dict(env)
    env_wrong["A"] = DenseRelation(jax.device_put(env["A"].data, wrong), 2)
    assert set(_committed_layouts(env_wrong)) == {"A"}
    with pytest.warns(ReshardWarning):
        comp(env_wrong)
    nbytes = int(env["A"].data.nbytes)
    assert comp.counters["reshard"]["resharded_calls"] == 1
    assert comp.counters["reshard"]["last_call_bytes"] == nbytes
    with warnings.catch_warnings():
        warnings.simplefilter("error", ReshardWarning)  # once per entry
        comp(env_wrong)
    assert comp.counters["reshard"]["bytes_moved"] == 2 * nbytes
    assert comp.counters["reshard"]["calls"] == comp.counters["reshard"]["resharded_calls"] + 0
    # matching layouts move nothing
    comp2 = low.compile(mesh=mesh, committed=_committed_layouts(env))
    comp2(env)
    assert comp2.counters["reshard"]["last_call_bytes"] == 0
    # committed *replicated* inputs shard by a local slice — zero bytes
    # moved, no warning (and plan_join's _move fold charges them nothing)
    env_rep = dict(env)
    env_rep["A"] = DenseRelation(
        jax.device_put(env["A"].data, NamedSharding(mesh, P())), 2
    )
    comp3 = low.compile(mesh=mesh, donate=("B",))  # fresh cache entry
    with warnings.catch_warnings():
        warnings.simplefilter("error", ReshardWarning)
        comp3(env_rep)
    assert comp3.counters["reshard"]["last_call_bytes"] == 0


# ---------------------------------------------------------------------------
# Fused join-aggregate: Σ(COO ⋈ Dense) in blocks of nnz rows, per shard
# ---------------------------------------------------------------------------


def _small_blocks(monkeypatch, rows: int, d: int) -> None:
    """Blocks of ``rows`` nnz rows at width ``d`` (f32)."""
    from repro.core import compiler

    monkeypatch.setattr(compiler, "JOIN_AGG_BLOCK_BYTES", rows * d * 4)
    monkeypatch.setattr(compiler, "BLOCK_ROWS_QUANTUM", 8)


@pytest.mark.parametrize("nnz", [1000, 1003])
def test_blocked_join_agg_matches_one_block(monkeypatch, nnz):
    """With the block small, the forward Σ_dst(Edge ⋈ Node), ∂Node (the
    reversed-edge Σ) and ∂Edge (the per-edge dot) run in ≥ 3 blocks, a
    ragged last one among them at 1003 rows, and match the one-block
    lowering to 1e-5, -1 pad rows included."""
    rng = np.random.default_rng(11)
    env = gcn_env(rng, n=50, nnz=nnz, d=8)
    keys = np.asarray(env["Edge"].keys).copy()
    keys[-9:] = COO_PAD_KEY
    env["Edge"] = CooRelation(jnp.asarray(keys), env["Edge"].values, (50, 50))
    prog = gcn_grad_prog()
    out_1, grads_1 = RAEngine(prog).lower(env).compile()(env)
    _small_blocks(monkeypatch, 296, 8)
    low = RAEngine(prog).lower(env)
    notes = [k for k in low.resolutions if k.startswith("join_agg[")]
    assert notes and all("blocks=4" in k for k in notes), notes
    out_b, grads_b = low.compile()(env)
    np.testing.assert_allclose(out_b.data, out_1.data, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        grads_b["Node"].data, grads_1["Node"].data, rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        grads_b["Edge"].values, grads_1["Edge"].values, rtol=1e-5, atol=1e-5
    )
    assert np.all(np.asarray(grads_b["Edge"].values)[-9:] == 0.0)


def test_replicating_edges_is_infeasible_when_their_messages_bust_the_budget():
    """Replicating a COO makes every device produce all its messages (nnz
    × the dense row): over the per-device budget the nnz rows stay on the
    data axes even where the scatter costs more than replication."""
    env = {"Edge": _coo(100_000), "Node": DenseRelation(jnp.zeros((64, 8), jnp.float32), 1)}
    q = gcn_query()
    (free,) = plan_query(q, env, 2, geometry=GEO, mem_budget=1e9).values()
    assert "data:replicate" in free.costs
    # 100,000 edges × 8 × 4 B = 3.2 MB of messages over a 3 MB budget
    (tight,) = plan_query(q, env, 2, geometry=GEO, mem_budget=3e6).values()
    assert "data:replicate" not in tight.costs
    assert tight.data_kind == "data:shard_nnz_left"


_SHARDED_GCN_SCRIPT = textwrap.dedent(
    """
    import os, re, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import repro
    from repro.core import compiler
    from repro.core.relation import pad_coo_nnz
    from repro.launch.mesh import make_host_mesh
    from repro.relational import gcn_conv, partitioned_edges, rel_linear

    case = sys.argv[1]
    rng = np.random.default_rng(5)
    n, nnz, d = 64, 1800, 8
    keys = np.stack([rng.integers(0, n, nnz), rng.integers(0, n, nnz)], 1)
    w = (rng.normal(size=nnz) / 10).astype(np.float32)
    edge = partitioned_edges(keys.astype(np.int32), w, n, 4)
    if case == "empty_shard":
        # the last quarter of the nnz rows is padding: a shard with no edges
        edge = pad_coo_nnz(edge, 2400)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    W = jnp.asarray(rng.normal(size=(d, 5)), jnp.float32)

    def loss(W, x, k, w):
        h = jax.nn.relu(rel_linear(gcn_conv(x, k, w), W))
        return jnp.sum(gcn_conv(h, k, w) ** 2)

    l1, g1 = jax.jit(jax.value_and_grad(loss))(W, x, edge.keys, edge.values)
    compiler.JOIN_AGG_BLOCK_BYTES = 100 * d * 4
    compiler.BLOCK_ROWS_QUANTUM = 16
    if case == "exchange":
        compiler.exchange = lambda part, axes, *, scatter: part[: part.shape[0] // 4]
    mesh = make_host_mesh(model=1)
    k4 = jax.device_put(edge.keys, NamedSharding(mesh, P("data", None)))
    w4 = jax.device_put(edge.values, NamedSharding(mesh, P("data")))
    db = repro.Database(mesh=mesh)
    with db.activate():
        step = jax.jit(jax.value_and_grad(loss))
        l4, g4 = step(W, x, k4, w4)
        text = step.lower(W, x, k4, w4).compile().as_text()
    sites = [k for k in db.resolutions() if k.startswith("join_agg[")]
    e4 = edge.nnz // 4
    assert sites and all(f"E={e4}," in k and "blocks=1," not in k for k in sites), sites
    same = np.allclose(l4, l1, rtol=1e-5) and np.allclose(g4, g1, rtol=1e-5, atol=1e-5)
    if case == "exchange":
        assert not same, (l1, l4)
    else:
        assert same, (l1, l4, np.abs(g4 - g1).max())
        assert "reduce-scatter" in text
        for rows, cols in re.findall(r"f32\\[(\\d+),(\\d+)", text):
            assert not (int(rows) in (edge.nnz, e4) and int(cols) > 1), (rows, cols)
    print("SHARDED-GCN-OK")
    """
)


@pytest.mark.spmd
@pytest.mark.parametrize("case", ["edges", "empty_shard", "exchange"])
def test_gcn_conv_in_user_jit_runs_per_shard_on_four_devices(case):
    """On four virtual CPU devices, ``gcn_conv`` inside a user's jit under
    ``Database(mesh=...)`` on ``partitioned_edges`` runs its join-aggregates
    per shard in blocks (``join_agg`` notes a quarter of the edges and
    several blocks) and matches one device, a shard of padding alone
    included; the step holds a reduce-scatter and no edges × width array.
    With the exchange of partial sums replaced by each shard's own part,
    it disagrees."""
    repo = pathlib.Path(__file__).resolve().parent.parent
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_GCN_SCRIPT, case],
        capture_output=True,
        text=True,
        env={
            "PYTHONPATH": str(repo / "src"),
            "PATH": "/usr/bin:/bin",
            "JAX_PLATFORMS": "cpu",
        },
        cwd=str(repo),
        timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED-GCN-OK" in r.stdout
