"""2-D (data × model) query plans: MeshGeometry, per-relation batch-dim
placement, 1-axis bit-for-bit compatibility, the make_host_mesh fixes,
and — under the tier1-spmd lane's 8 virtual devices — the end-to-end
oracle: a compiled logreg grad step on a real 4×2 host mesh matches the
single-device result."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import fra
from repro.core.autodiff import ra_autodiff
from repro.core.engine import RAEngine
from repro.core.kernels import ADD, LOGISTIC, MATMUL, MUL, XENT
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
    MeshGeometry,
    input_pspecs,
    plan_query,
)
from repro.core.relation import DenseRelation
from repro.launch.mesh import batch_axes, make_host_mesh, resolve_mesh

requires8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (tier1-spmd lane: "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


def _sds(shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def logreg_forward_query():
    """Rx (batch × feature) ⋈ theta (feature) → Σ by batch row."""
    join = fra.Join(
        eq_pred((1, 0)), jproj(L(0), L(1)), MUL,
        fra.scan("Rx", 2), fra.scan("theta", 1),
    )
    return fra.Query(
        fra.Agg(project_key(0), ADD, join), inputs=("Rx", "theta")
    )


def logreg_loss_query():
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
    return fra.Query(f_loss, inputs=("theta",))


def matmul_query():
    join = fra.Join(
        eq_pred((1, 0)), jproj(L(0), L(1), R(1)), MATMUL,
        fra.scan("A", 2), fra.scan("B", 2),
    )
    return fra.Query(fra.Agg(project_key(0, 2), ADD, join), inputs=("A", "B"))


# ---------------------------------------------------------------------------
# 2-D cost model (device-free unit tests)
# ---------------------------------------------------------------------------


def test_2d_data_shards_batch_relation_model_shards_params():
    """The acceptance layout: the batch-keyed relation lands on the data
    axis, the parameter relation on the model axis (classic 2-D logreg)."""
    q = logreg_forward_query()
    env = {"Rx": _sds((4096, 64)), "theta": _sds((64,))}
    geo = MeshGeometry("model", 2, ("data",), 4)
    plans = plan_query(q, env, 2, geometry=geo)
    (plan,) = plans.values()

    # data axis: shard Rx's surviving batch dim (row), replicate theta
    assert plan.data_kind == "data:shard_left"
    assert plan.left_batch_dim == 0 and plan.right_batch_dim is None
    # batch key survives the Σ-by-row: no data-axis all-reduce
    assert not plan.needs_data_psum
    # model axis: co-partition on the feature key (theta on "model") —
    # a broadcast would leave the model axis idle (Rx's only surviving
    # dim is taken by "data") and is costed as full replication
    assert plan.kind == "copartition"
    assert plan.left_shard_dim == 1 and plan.right_shard_dim == 0
    assert plan.costs["copartition"] < plan.costs["broadcast_right"]

    specs = input_pspecs(q, plans)
    assert specs["Rx"] == P("data", "model")
    assert specs["theta"] == P("model")


def test_2d_data_replicates_when_nothing_has_a_batch_dim():
    """Neither side of the loss join keeps a non-contraction dim — the
    data axes have nothing to shard and fall back to replication."""
    q = logreg_loss_query()
    env = {
        "Rx": _sds((4096, 64)),
        "Ry": _sds((4096,)),
        "theta": _sds((64,)),
    }
    geo = MeshGeometry("model", 2, ("data",), 4)
    plans = plan_query(q, env, 2, geometry=geo)
    loss_plans = [
        p for p in plans.values() if p.data_kind == "data:replicate"
    ]
    assert loss_plans, "xent join should have no batch dim to shard"
    (loss_plan,) = loss_plans
    assert loss_plan.left_batch_dim is None
    assert loss_plan.right_batch_dim is None


def test_2d_data_axis_respects_memory_budget():
    """Candidates that would replicate an over-budget relation over the
    data axes are infeasible; with nothing feasible the planner falls
    back to sharding a batch dim (never an error on the data axes)."""
    q = logreg_forward_query()
    env = {"Rx": _sds((4096, 64)), "theta": _sds((64,))}
    geo = MeshGeometry("model", 2, ("data",), 4)
    plans = plan_query(q, env, 2, mem_budget=1.0, geometry=geo)
    (plan,) = plans.values()
    # theta (256 B) exceeds the 1-byte budget: replicate is infeasible,
    # best-effort still shards Rx's batch dim
    assert plan.data_kind == "data:shard_left"
    assert "data:replicate" not in plan.costs


def test_from_mesh_rejects_absent_axis_override():
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1), ("model",)
    )
    with pytest.raises(ValueError, match="not on the mesh"):
        MeshGeometry.from_mesh(mesh, axis="tp")


def test_resolve_mesh_rejects_unknown_production_variant():
    with pytest.raises(ValueError, match="production mesh variant"):
        resolve_mesh("production:multipods")


def test_one_axis_geometry_reproduces_1d_plans_bit_for_bit():
    """A 1-axis mesh is the legacy planner: identical JoinPlans (kind,
    dims, every cost-table entry) and identical PartitionSpecs."""
    q = matmul_query()
    for env in (
        {"A": _sds((512, 512, 256, 256)), "B": _sds((512, 1, 256, 64))},
        {"A": _sds((512, 512, 256, 256)), "B": _sds((512, 512, 256, 256))},
    ):
        legacy = plan_query(q, env, 16)
        one_axis = plan_query(
            q, env, 16, geometry=MeshGeometry.single(16)
        )
        assert legacy == one_axis
        assert input_pspecs(q, legacy) == input_pspecs(q, one_axis)
        for plan in one_axis.values():
            assert plan.data_kind == "none"
            assert plan.left_batch_dim is None
            assert plan.right_batch_dim is None
            assert not any(k.startswith("data:") for k in plan.costs)


def test_multipod_folds_pod_and_data_axes():
    """On the multi-pod geometry the batch dim carries the folded
    ("pod", "data") pair, matching launch/mesh.batch_axes."""
    q = logreg_forward_query()
    env = {"Rx": _sds((4096, 64)), "theta": _sds((64,))}
    geo = MeshGeometry("model", 16, ("pod", "data"), 32)
    plans = plan_query(q, env, 16, geometry=geo)
    specs = input_pspecs(q, plans)
    assert tuple(specs["Rx"])[0] == ("pod", "data")


def test_geometry_from_one_axis_mesh_degrades_to_1d():
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1), ("model",)
    )
    geo = MeshGeometry.from_mesh(mesh)
    assert geo.model_axis == "model"
    assert geo.data_axes == () and geo.data_size == 1
    assert geo.data_spec is None


# ---------------------------------------------------------------------------
# make_host_mesh fixes
# ---------------------------------------------------------------------------


def test_make_host_mesh_raises_value_error_with_device_count(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [object()] * 3)
    with pytest.raises(ValueError, match="3 visible device"):
        make_host_mesh(model=2)


def test_make_host_mesh_single_device_falls_back_to_1_axis(monkeypatch):
    real = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real[:1])
    mesh = make_host_mesh()
    assert tuple(mesh.axis_names) == ("model",)
    assert dict(mesh.shape) == {"model": 1}
    # the 1-axis fallback reproduces the legacy planner geometry
    geo = MeshGeometry.from_mesh(mesh)
    assert geo == MeshGeometry.single(1)


def test_resolve_mesh_specs(monkeypatch):
    real = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real[:1])
    assert resolve_mesh(None) is None
    mesh = resolve_mesh("host")
    assert resolve_mesh(mesh) is mesh
    with pytest.raises(ValueError, match="unknown mesh spec"):
        resolve_mesh("nope")


def test_host_meshes_have_auto_axes(monkeypatch):
    # Explicit axes (jax.make_mesh's default) would make the co-partition
    # plan's sharded contraction a ShardingTypeError
    from jax.sharding import AxisType

    real = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real[:1])
    assert set(make_host_mesh().axis_types) == {AxisType.Auto}
    assert set(resolve_mesh("host").axis_types) == {AxisType.Auto}


# ---------------------------------------------------------------------------
# SPMD: the 4×2 host mesh (tier1-spmd lane, 8 virtual CPU devices)
# ---------------------------------------------------------------------------


def _logreg_env(rng, n=64, m=8):
    return {
        "Rx": DenseRelation(jnp.asarray(rng.normal(size=(n, m)), jnp.float32), 2),
        "Ry": DenseRelation(
            jnp.asarray(rng.integers(0, 2, size=n), jnp.float32), 1
        ),
        "theta": DenseRelation(
            jnp.asarray(rng.normal(size=m) * 0.1, jnp.float32), 1
        ),
    }


@pytest.mark.spmd
@requires8
def test_logreg_grad_step_2d_matches_single_device_oracle():
    """Acceptance: on the 4×2 (data × model) host mesh a compiled logreg
    grad step plans 2-D shardings — batch relation on "data", parameter
    relation on "model" — and matches the unsharded result to 1e-5."""
    mesh = make_host_mesh(model=2)
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    assert batch_axes(mesh) == ("data",)
    geo = MeshGeometry.from_mesh(mesh)
    assert geo == MeshGeometry("model", 2, ("data",), 4)

    prog = ra_autodiff(logreg_loss_query())
    env = _logreg_env(np.random.default_rng(0))
    eng = RAEngine(prog)
    low = eng.lower(env)

    comp2d = low.compile(mesh=mesh)
    assert comp2d.placements["Rx"] == {"data": 0, "model": 1}
    assert comp2d.placements["theta"] == {"data": None, "model": 0}
    out2, grads2 = comp2d(env)
    walks = eng.trace_count
    comp2d(env)                          # jit cache hit: no re-lowering
    assert eng.trace_count == walks

    comp1 = low.compile()                # single-device oracle
    out1, grads1 = comp1(env)
    np.testing.assert_allclose(
        np.asarray(out2.data), np.asarray(out1.data), atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(grads2["theta"].data),
        np.asarray(grads1["theta"].data),
        atol=1e-5,
    )
    # the co-partitioned feature key must have produced a psum
    hlo = comp2d.lower_text()
    assert "all-reduce" in hlo or "reduce-scatter" in hlo


@pytest.mark.spmd
@requires8
def test_compile_cache_distinguishes_mesh_geometries():
    prog = ra_autodiff(logreg_loss_query())
    env = _logreg_env(np.random.default_rng(1))
    low = RAEngine(prog).lower(env)
    m22 = make_host_mesh(model=2)
    m81 = make_host_mesh(model=1)
    c22 = low.compile(mesh=m22)
    c81 = low.compile(mesh=m81)
    assert c22 is not c81
    assert c22.geometry != c81.geometry
    assert low.compile(mesh=m22) is c22   # same mesh: cache hit


@pytest.mark.spmd
@requires8
def test_relational_wrappers_under_session_mesh():
    """The relational operator layer threads the canonical host mesh via
    an activated session — forward and backward match the mesh-less
    result (the custom_vjp boundary takes no new arguments)."""
    import repro
    from repro.relational.linear import rel_matmul_blocked

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(4, 2, 8, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 4, 8, 8)), jnp.float32)

    def loss(x, w):
        return jnp.sum(rel_matmul_blocked(x, w) ** 2)

    ref = rel_matmul_blocked(x, w)
    gref = jax.grad(loss, argnums=(0, 1))(x, w)
    with repro.Database(mesh="host:2").activate():
        out = rel_matmul_blocked(x, w)
        g = jax.grad(loss, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(g[0]), np.asarray(gref[0]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(g[1]), np.asarray(gref[1]), atol=1e-4)
