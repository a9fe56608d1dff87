"""Kernel dispatch (core/kernels.py registry + engine threading).

Covers the registry contract — tier resolution order, backend gating,
predicate fall-through — the numerical agreement of the CPU tiers
(Pallas interpret-mode vs the ref.py oracles, forward *and* gradient),
and the staging contract: the DispatchTable is part of the lowering
signature, so switching tiers invalidates the engine's lowering cache
while re-using a tier hits it.
"""

import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiler, fra
from repro.core import kernels as K
from repro.core.autodiff import ra_autodiff
from repro.core.engine import RAEngine
from repro.core.kernels import ADD, LOGISTIC, MUL, XENT
from repro.core.keys import (
    EMPTY_KEY,
    TRUE,
    L,
    eq_pred,
    identity_key,
    jproj,
    project_key,
)
from repro.core.relation import CooRelation, DenseRelation
from repro.relational.gcn import gcn_square_loss

CPU_TIERS = ("jnp", "ref", "interpret")


# ---------------------------------------------------------------------------
# Registry resolution order
# ---------------------------------------------------------------------------


def test_default_table_is_jnp_on_cpu():
    t = K.default_table("cpu")
    for op in K.DISPATCH_OPS:
        assert t.tiers(op) == ("jnp",)
        assert K.resolve_impl(op, {"dtype": jnp.float32}, t).tier == "jnp"


def test_default_table_prefers_pallas_on_tpu():
    t = K.default_table("tpu")
    for op in K.DISPATCH_OPS:
        assert t.tiers(op) == ("pallas", "jnp")
        # resolution honours the table's pinned backend, not the host's
        assert K.resolve_impl(op, {"dtype": jnp.float32}, t).tier == "pallas"


@pytest.mark.parametrize("tier", CPU_TIERS)
def test_forced_tier_resolves_that_tier(tier):
    t = K.make_table(tier, backend="cpu")
    for op in K.DISPATCH_OPS:
        assert K.resolve_impl(op, {"dtype": jnp.float32}, t).tier == tier


def test_tier_order_walked_in_sequence():
    t = K.make_table(("interpret", "ref", "jnp"), backend="cpu")
    impl = K.resolve_impl("segment_sum", {"dtype": jnp.float32}, t)
    assert impl.tier == "interpret"
    # int dtype fails the interpret predicate → falls through to ref
    impl = K.resolve_impl("segment_sum", {"dtype": jnp.int32}, t)
    assert impl.tier == "ref"


def test_pallas_tier_is_tpu_only():
    t = K.make_table("pallas", backend="cpu")
    with pytest.raises(K.KernelDispatchError):
        K.resolve_impl("blocked_matmul", {"dtype": jnp.float32}, t)


def test_make_table_validates():
    with pytest.raises(ValueError, match="unknown tier"):
        K.make_table("mxu")
    with pytest.raises(ValueError, match="unknown op"):
        K.make_table({"softmax": "jnp"})
    with pytest.raises(TypeError):
        K.make_table(3.14)


def test_make_table_rejects_cross_backend_reinterpretation():
    tpu_table = K.default_table("tpu")
    assert K.make_table(tpu_table) is tpu_table          # passthrough
    assert K.make_table(tpu_table, backend="tpu") is tpu_table
    with pytest.raises(ValueError, match="pinned to backend"):
        K.make_table(tpu_table, backend="cpu")


def test_make_table_dict_keeps_defaults_for_unmentioned_ops():
    t = K.make_table({"segment_sum": "ref"}, backend="cpu")
    assert t.tiers("segment_sum") == ("ref",)
    assert t.tiers("blocked_matmul") == ("jnp",)


def test_tables_are_hashable_and_compare_by_value():
    a = K.make_table("ref", backend="cpu")
    b = K.make_table("ref", backend="cpu")
    assert a == b and hash(a) == hash(b)
    assert a != K.make_table("jnp", backend="cpu")


# ---------------------------------------------------------------------------
# CPU tiers: interpret-mode vs ref.py, forward + gradient
# ---------------------------------------------------------------------------


def test_segment_sum_interpret_matches_ref_fwd_and_grad():
    from repro.kernels.segsum.ops import segment_sum
    from repro.kernels.segsum.ref import segment_sum_ref

    rng = np.random.default_rng(0)
    e, d, s = 75, 12, 17
    msg = jnp.asarray(rng.normal(size=(e, d)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, s, size=e), jnp.int32)

    got = segment_sum(msg, seg, s, interpret=True)
    ref = segment_sum_ref(msg, seg, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

    def loss_pallas(m):
        return jnp.sum(segment_sum(m, seg, s, interpret=True) ** 2)

    def loss_ref(m):
        return jnp.sum(segment_sum_ref(m, seg, s) ** 2)

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss_pallas)(msg)),
        np.asarray(jax.grad(loss_ref)(msg)),
        rtol=1e-5,
        atol=1e-5,
    )


def test_segment_sum_interpret_tier_sorts_unsorted_padded_ids_fwd_and_grad():
    """The interpret tier as the registry resolves it, on COO-like ids:
    unsorted, a hub over several edge blocks, -1 padding and ids out of
    range, E and S not multiples of the kernel's tiles."""
    rng = np.random.default_rng(2)
    e, d, s = 1400, 12, 300
    seg = rng.integers(0, s, size=e)
    seg[rng.random(e) < 0.5] = 17
    seg[rng.random(e) < 0.1] = -1
    seg[rng.random(e) < 0.05] = s + 3
    seg = jnp.asarray(seg, jnp.int32)
    msg = jnp.asarray(rng.normal(size=(e, d)), jnp.float32)
    fn = K.resolve_impl(
        "segment_sum", {"dtype": jnp.float32},
        K.make_table(("interpret",), backend="cpu"),
    ).fn
    ref = jax.ops.segment_sum(msg, seg, num_segments=s)
    np.testing.assert_allclose(
        np.asarray(fn(msg, seg, s)), np.asarray(ref), rtol=1e-5, atol=1e-4
    )
    w = jnp.asarray(rng.normal(size=(s, d)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(jax.grad(lambda m: jnp.sum(fn(m, seg, s) * w))(msg)),
        np.asarray(jax.grad(
            lambda m: jnp.sum(jax.ops.segment_sum(m, seg, num_segments=s) * w)
        )(msg)),
        rtol=1e-6,
        atol=1e-6,
    )


def test_blocked_matmul_interpret_matches_ref_fwd_and_grad():
    from repro.kernels.matmul.ops import blocked_matmul
    from repro.kernels.matmul.ref import matmul_ref

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(33, 20)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(20, 17)), jnp.float32)

    got = blocked_matmul(x, y, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(matmul_ref(x, y)), rtol=1e-5, atol=1e-5
    )

    def loss_pallas(a, b):
        return jnp.sum(blocked_matmul(a, b, interpret=True) ** 2)

    def loss_ref(a, b):
        return jnp.sum(matmul_ref(a, b) ** 2)

    for argnum in (0, 1):
        np.testing.assert_allclose(
            np.asarray(jax.grad(loss_pallas, argnum)(x, y)),
            np.asarray(jax.grad(loss_ref, argnum)(x, y)),
            rtol=1e-4,
            atol=1e-4,
        )


# ---------------------------------------------------------------------------
# Engine-level agreement: compiled programs under every CPU tier
# ---------------------------------------------------------------------------


def _logreg_prog_env():
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
        fra.Join(
            eq_pred((0, 0)), jproj(L(0)), XENT, f_predict, fra.const("Ry", 1)
        ),
    )
    prog = ra_autodiff(fra.Query(f_loss, inputs=("theta",)))
    rng = np.random.default_rng(2)
    n, m = 48, 12
    env = {
        "Rx": DenseRelation(jnp.asarray(rng.normal(size=(n, m)), jnp.float32), 2),
        "Ry": DenseRelation(
            jnp.asarray(rng.integers(0, 2, size=n), jnp.float32), 1
        ),
        "theta": DenseRelation(
            jnp.asarray(rng.normal(size=m) * 0.1, jnp.float32), 1
        ),
    }
    return prog, env


def _gcn_prog_env():
    prog = ra_autodiff(gcn_square_loss(const_edges=True))
    rng = np.random.default_rng(3)
    n, nnz, d = 16, 40, 8
    src = rng.integers(0, n, size=nnz)
    dst = rng.integers(0, n, size=nnz)
    env = {
        "Edge": CooRelation(
            jnp.asarray(np.stack([src, dst], 1), jnp.int32),
            jnp.asarray(rng.normal(size=nnz), jnp.float32),
            (n, n),
        ),
        "Node": DenseRelation(
            jnp.asarray(rng.normal(size=(n, d)), jnp.float32), 1
        ),
    }
    return prog, env


@pytest.mark.parametrize("make", [_logreg_prog_env, _gcn_prog_env])
@pytest.mark.parametrize("tier", ("ref", "interpret"))
def test_compiled_grad_step_matches_jnp_tier(make, tier):
    prog, env = make()
    eng = RAEngine(prog)
    out_j, grads_j = eng.lower(env, dispatch="jnp").compile()(env)
    out_t, grads_t = eng.lower(env, dispatch=tier).compile()(env)
    np.testing.assert_allclose(
        np.asarray(out_t.data), np.asarray(out_j.data), rtol=1e-5, atol=1e-5
    )
    for name in grads_j:
        gj, gt = grads_j[name], grads_t[name]
        lj = gj.values if isinstance(gj, CooRelation) else gj.data
        lt = gt.values if isinstance(gt, CooRelation) else gt.data
        np.testing.assert_allclose(
            np.asarray(lt), np.asarray(lj), rtol=1e-5, atol=1e-5
        )


def test_resolutions_record_the_forced_tier():
    prog, env = _gcn_prog_env()
    comp = RAEngine(prog).lower(env, dispatch="ref").compile()
    res = comp.resolutions
    assert res, "no dispatch site recorded for the GCN program"
    segsums = [k for k in res if k.startswith("segment_sum[")]
    # the forward conv and the reverse-edge gradient conv share a shape
    # signature but are distinct sites: both must be recorded (#2 suffix)
    assert len(segsums) >= 2
    assert set(res.values()) == {"ref"}
    assert comp.dispatch == K.make_table("ref")


def test_grad_eval_accepts_dispatch():
    prog, env = _logreg_prog_env()
    out_j, grads_j = compiler.grad_eval(prog, env)
    out_r, grads_r = compiler.grad_eval(prog, env, dispatch="ref")
    np.testing.assert_allclose(
        np.asarray(out_r.data), np.asarray(out_j.data), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(grads_r["theta"].data),
        np.asarray(grads_j["theta"].data),
        rtol=1e-5,
        atol=1e-5,
    )


# ---------------------------------------------------------------------------
# Staging contract: dispatch is part of the lowering signature
# ---------------------------------------------------------------------------


def test_switching_tiers_invalidates_lowering_cache():
    prog, env = _logreg_prog_env()
    eng = RAEngine(prog)

    low_jnp = eng.lower(env, dispatch="jnp")
    assert eng.trace_count == 1
    assert eng.lower(env, dispatch="jnp") is low_jnp    # same tier: hit
    assert eng.trace_count == 1

    low_ref = eng.lower(env, dispatch="ref")            # tier switch: miss
    assert low_ref is not low_jnp
    assert eng.trace_count == 2

    assert eng.lower(env, dispatch="ref") is low_ref    # and re-hit
    assert eng.trace_count == 2


def test_compiled_steps_per_tier_are_independent_and_cached():
    prog, env = _logreg_prog_env()
    eng = RAEngine(prog)
    comp_jnp = eng.lower(env, dispatch="jnp").compile()
    comp_ref = eng.lower(env, dispatch="ref").compile()
    assert comp_jnp is not comp_ref

    comp_jnp(env)
    comp_ref(env)
    walks = eng.trace_count
    for _ in range(2):                       # steady state: zero re-walks
        comp_jnp(env)
        comp_ref(env)
    assert eng.trace_count == walks
    assert eng.lower(env, dispatch="ref").compile() is comp_ref


# ---------------------------------------------------------------------------
# Multi-device meshes: no Pallas call reaches XLA's SPMD partitioner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "size, want", [(None, ("pallas", "jnp")), (1, ("pallas", "jnp")), (4, ("jnp",))]
)
def test_table_for_mesh_drops_pallas_on_multi_device_meshes(size, want):
    from types import SimpleNamespace

    mesh = None if size is None else SimpleNamespace(size=size)
    t = K.table_for_mesh(K.default_table("tpu"), mesh)
    assert t.backend == "tpu"
    for op in K.DISPATCH_OPS:
        assert t.tiers(op) == want
    # tiers other than pallas keep their order
    forced = K.make_table(("pallas", "interpret"), backend="cpu")
    kept = ("interpret",) if size == 4 else ("pallas", "interpret")
    assert K.table_for_mesh(forced, mesh).tiers("segment_sum") == kept


def test_table_for_mesh_keeps_the_table_for_ops_run_per_shard():
    """The per-shard view of a multi-device table is the table as given:
    inside shard_map each device runs its shard as on one chip."""
    from types import SimpleNamespace

    t = K.default_table("tpu")
    four = K.table_for_mesh(t, SimpleNamespace(size=4))
    assert four.per_shard() == t
    assert K.table_for_mesh(four, SimpleNamespace(size=4)) == four
    assert K.table_for_mesh(t, SimpleNamespace(size=1)).per_shard() == t
    # a table with nothing to drop keeps no separate per-shard view
    jnp_only = K.make_table("jnp", backend="tpu")
    assert K.table_for_mesh(jnp_only, SimpleNamespace(size=4)) == jnp_only


ARXIV_NODES = 169_343
ARXIV_EDGES = 1_166_243 + ARXIV_NODES


def test_join_agg_is_one_block_at_arxiv_shape():
    """At ogbn-arxiv shape every join-aggregate of the GCN's step fits one
    block (1,335,586 edges × 256 × 4 B = 1.37 GB of messages): its note
    says so, and the call is lowered as one gather, ⊗ and Σ."""
    sds = jax.ShapeDtypeStruct
    env = {
        "Edge": CooRelation(sds((ARXIV_EDGES, 2), jnp.int32),
                            sds((ARXIV_EDGES,), jnp.float32),
                            (ARXIV_NODES, ARXIV_NODES)),
        "Node": DenseRelation(sds((ARXIV_NODES, 256), jnp.float32), 1),
    }
    low = RAEngine(ra_autodiff(gcn_square_loss())).lower(
        env, dispatch=K.make_table(None, backend="tpu"))
    notes = {k: v for k, v in low.resolutions.items() if k.startswith("join_agg[")}
    assert notes and set(notes.values()) == {"pallas"}, low.resolutions
    assert all(k.split("#")[0] == f"join_agg[E={ARXIV_EDGES},D=256,"
               f"S={ARXIV_NODES},blocks=1]" for k in notes), notes
    assert any(k.startswith(f"segment_sum[E={ARXIV_EDGES},") for k in low.resolutions)


_MESH_TABLE_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import kernels as K
    from repro.core.autodiff import ra_autodiff
    from repro.core.engine import RAEngine
    from repro.core.relation import CooRelation, DenseRelation
    from repro.launch.mesh import make_host_mesh
    from repro.relational import gcn_square_loss

    prog = ra_autodiff(gcn_square_loss())
    rng = np.random.default_rng(0)
    n, nnz, d = 16, 64, 8
    env = {
        "Edge": CooRelation(
            jnp.asarray(rng.integers(0, n, size=(nnz, 2)), jnp.int32),
            jnp.asarray(rng.normal(size=nnz), jnp.float32), (n, n)),
        "Node": DenseRelation(jnp.asarray(rng.normal(size=(n, d)), jnp.float32), 1),
    }

    # a TPU table lowers to Pallas sites (abstractly: nothing runs) ...
    low = RAEngine(prog).lower(env, dispatch=K.make_table(None, backend="tpu"))
    assert set(low.resolutions.values()) == {"pallas"}, low.resolutions
    # ... a four-device mesh compiles the sites XLA's partitioner splits
    # as jnp, and keeps Pallas only for the sites that run per shard
    # inside shard_map: here every site, the edges being nnz-sharded
    comp = low.compile(mesh=make_host_mesh(model=1))
    assert "pallas" not in comp.dispatch.describe(), comp.dispatch
    assert comp.dispatch.per_shard().tiers("gather_join")[0] == "pallas"
    # the step records its per-shard sites as it is traced (abstractly
    # here: a TPU Pallas step does not lower for the CPU)
    jax.eval_shape(comp._jitted, {}, env, None)
    assert set(comp.resolutions.values()) == {"pallas"}, comp.resolutions
    assert any(k.startswith("join_agg[E=16,") for k in comp.resolutions)
    # the backend's own table runs them on the mesh as on one device
    comp = RAEngine(prog).lower(env).compile(mesh=make_host_mesh(model=1))
    out, grads = comp(env)
    assert set(comp.resolutions.values()) == {"jnp"}, comp.resolutions
    ref_out, ref_grads = RAEngine(prog).lower(env).compile()(env)
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(ref_out.data),
                               rtol=1e-5, atol=1e-5)
    for name in ("Edge", "Node"):
        g, r = grads[name], ref_grads[name]
        g = g.values if name == "Edge" else g.data
        r = r.values if name == "Edge" else r.data
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-5, atol=1e-5)
    print("MESH-TABLE-OK")
    """
)


@pytest.mark.spmd
def test_multi_device_compile_runs_jnp_tier_in_place_of_pallas():
    repo = pathlib.Path(__file__).resolve().parent.parent
    r = subprocess.run(
        [sys.executable, "-c", _MESH_TABLE_SCRIPT],
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
    assert "MESH-TABLE-OK" in r.stdout


def test_database_resolutions_keep_every_tier_a_site_resolved_to():
    import repro

    _, env = _gcn_prog_env()
    db = repro.Database(dispatch="jnp")
    db.put("Edge", env["Edge"])
    db.put("Node", env["Node"].data, keys=("node",))
    first = db.query(gcn_square_loss())
    first.step(wrt=("Node",))
    db.dispatch = K.make_table("ref")
    second = db.query(gcn_square_loss())
    second.step(wrt=("Node",))
    res = db.resolutions()
    sites = [k for k in res if k.split("[", 1)[0] in K.DISPATCH_OPS]
    assert sites, res
    # both steps are live and share their site keys: neither hides the other
    assert {res[k] for k in sites} == {("jnp", "ref")}, res
    assert set(first.resolutions.values()) == {"jnp"}
    assert set(second.resolutions.values()) == {"ref"}
