"""Staged engine (core/engine.py): lower → plan → jit-compile.

Covers the staging contract — same-shape re-execution hits the lowering
cache (trace-counter stays flat), changed shapes re-lower — and the
numerics: Compiled output matches the sparse interpreter oracle on the
logreg and GCN queries. The SPMD subprocess test is the acceptance path:
plan_query's PartitionSpecs become jax.jit in_shardings and the chosen
co-partition plan's all-reduce shows up in the HLO.
"""

import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compiler, fra, interpreter
from repro.core.autodiff import ra_autodiff
from repro.core.engine import RAEngine, _staged_execute, engine_for
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
from repro.core.relation import (
    CooRelation,
    DenseRelation,
    from_blocked,
    to_blocked,
)

jax.config.update("jax_enable_x64", True)


def matmul_query():
    join = fra.Join(
        eq_pred((1, 0)),
        jproj(L(0), L(1), R(1)),
        MATMUL,
        fra.scan("A", 2),
        fra.scan("B", 2),
    )
    return fra.Query(fra.Agg(project_key(0, 2), ADD, join), inputs=("A", "B"))


def logreg_query():
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


def gcn_query():
    join = fra.Join(
        eq_pred((0, 0)),
        jproj(L(1)),
        MUL,
        fra.const("Edge", 2),
        fra.scan("Node", 1),
    )
    return fra.Query(fra.Agg(identity_key(1), ADD, join), inputs=("Node",))


def _matmul_env(rng, bi=2, bk=2, bj=2, c=3):
    A = rng.normal(size=(bi * c, bk * c))
    B = rng.normal(size=(bk * c, bj * c))
    return A, B, {"A": from_blocked(A, (c, c)), "B": from_blocked(B, (c, c))}


# ---------------------------------------------------------------------------
# Staging contract: the lowering cache and the trace counter
# ---------------------------------------------------------------------------


def test_same_shape_reexecution_hits_lowering_cache():
    rng = np.random.default_rng(0)
    _, _, env = _matmul_env(rng)
    eng = RAEngine(matmul_query())

    low = eng.lower(env)
    assert eng.trace_count == 1          # the abstract-shape lowering walk
    assert eng.lower(env) is low         # cache hit: no re-walk
    assert eng.trace_count == 1

    comp = low.compile()
    comp(env)                            # first call: one jit trace
    walks = eng.trace_count
    for _ in range(3):
        comp(env)                        # same signature: zero re-lowering
    assert eng.trace_count == walks
    assert low.compile() is comp         # Compiled is cached too


def test_changed_shapes_relower():
    rng = np.random.default_rng(1)
    _, _, env_small = _matmul_env(rng, c=3)
    _, _, env_big = _matmul_env(rng, c=4)
    eng = RAEngine(matmul_query())

    low_small = eng.lower(env_small)
    low_big = eng.lower(env_big)
    assert low_small is not low_big
    assert eng.trace_count == 2          # one walk per signature

    out = low_big.compile()(env_big)
    assert out.chunk_shape == (4, 4)


def test_compiled_rejects_mismatched_signature():
    rng = np.random.default_rng(2)
    _, _, env = _matmul_env(rng, c=3)
    _, _, other = _matmul_env(rng, c=4)
    comp = RAEngine(matmul_query()).lower(env).compile()
    with pytest.raises(ValueError, match="signature"):
        comp(other)


def test_staged_execute_caches_engines():
    q = matmul_query()
    assert engine_for(q) is engine_for(q)
    rng = np.random.default_rng(3)
    A, B, env = _matmul_env(rng)
    out = _staged_execute(q, env)
    np.testing.assert_allclose(to_blocked(out), A @ B, rtol=1e-8)


# ---------------------------------------------------------------------------
# Numerics: Compiled vs the sparse interpreter oracle
# ---------------------------------------------------------------------------


def test_compiled_logreg_matches_interpreter_oracle():
    rng = np.random.default_rng(4)
    X = rng.normal(size=(6, 3))
    y = rng.integers(0, 2, size=6).astype(float)
    theta = rng.normal(size=3) * 0.1
    env = {
        "Rx": DenseRelation(jnp.array(X), 2),
        "Ry": DenseRelation(jnp.array(y), 1),
        "theta": DenseRelation(jnp.array(theta), 1),
    }
    prog = ra_autodiff(logreg_query())

    eng = RAEngine(prog)
    out, grads = eng.lower(env).compile()(env)

    senv = {k: v.to_sparse() for k, v in env.items()}
    sout, sgrads = prog.eval(senv)       # tuple-at-a-time oracle

    assert float(out.data) == pytest.approx(sout[()], rel=1e-8)
    for (j,), v in sgrads["theta"].items():
        assert float(grads["theta"].data[j]) == pytest.approx(v, rel=1e-7)


def test_compiled_gcn_matches_interpreter_oracle():
    rng = np.random.default_rng(5)
    n, nnz, d = 8, 20, 4
    # unique (src, dst) pairs: the dict-backed oracle collapses duplicate
    # keys, whereas COO treats them as separate tuples to be aggregated
    flat = rng.choice(n * n, size=nnz, replace=False)
    src, dst = flat // n, flat % n
    w = rng.normal(size=nnz)
    H = rng.normal(size=(n, d))
    env = {
        "Edge": CooRelation(
            jnp.array(np.stack([src, dst], 1), dtype=jnp.int32),
            jnp.array(w),
            (n, n),
        ),
        "Node": DenseRelation(jnp.array(H), 1),
    }
    q = gcn_query()
    out = RAEngine(q).lower(env).compile()(env)

    senv = {k: v.to_sparse() for k, v in env.items()}
    sout = interpreter.run_query(q, senv)
    for (i,), vec in sout.items():
        np.testing.assert_allclose(
            np.asarray(out.data[i]), np.asarray(vec), rtol=1e-8
        )


def test_compiled_grad_program_matches_eager_wrapper():
    rng = np.random.default_rng(6)
    A, B, env = _matmul_env(rng)
    join = fra.Join(
        eq_pred((1, 0)), jproj(L(0), L(1), R(1)), MATMUL,
        fra.scan("A", 2), fra.scan("B", 2),
    )
    from repro.core.kernels import SQUARE, SUM_CHUNK

    prod = fra.Agg(project_key(0, 2), ADD, join)
    sq = fra.Select(TRUE, identity_key(2), SQUARE, prod)
    chunksum = fra.Select(TRUE, identity_key(2), SUM_CHUNK, sq)
    loss = fra.Agg(EMPTY_KEY, ADD, chunksum)
    prog = ra_autodiff(fra.Query(loss, inputs=("A", "B")))

    out_c, grads_c = RAEngine(prog).lower(env).compile()(env)
    out_e, grads_e = compiler.grad_eval(prog, env)

    np.testing.assert_allclose(float(out_c.data), float(out_e.data), rtol=1e-10)
    for name in ("A", "B"):
        np.testing.assert_allclose(
            to_blocked(grads_c[name]), to_blocked(grads_e[name]), rtol=1e-10
        )


def test_plans_are_populated_on_compile():
    """plan_query runs on the hot path: every Join in the forward query
    gets a physical plan, and the planner's specs are exposed."""
    rng = np.random.default_rng(7)
    _, _, env = _matmul_env(rng)
    comp = RAEngine(matmul_query()).lower(env).compile()
    assert len(comp.plans) == 1
    (plan,) = comp.plans.values()
    assert plan.kind in ("broadcast_left", "broadcast_right", "copartition")
    assert set(comp.input_specs) == {"A", "B"}


def test_compile_with_donation_runs():
    rng = np.random.default_rng(8)
    A, B, env = _matmul_env(rng)
    comp = RAEngine(matmul_query()).lower(env).compile(donate=("A",))
    out = comp(env)
    np.testing.assert_allclose(to_blocked(out), A @ B, rtol=1e-8)
    assert comp.donate_names == ("A",)


# ---------------------------------------------------------------------------
# Acceptance: planner-emitted in_shardings under SPMD (8 fake CPU devices;
# subprocess because the device count must be set before JAX initializes)
# ---------------------------------------------------------------------------

_SPMD_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fra
    from repro.core.autodiff import ra_autodiff
    from repro.core.engine import RAEngine
    from repro.core.kernels import ADD, MATMUL, MUL
    from repro.core.keys import L, R, eq_pred, identity_key, jproj, project_key
    from repro.core.relation import CooRelation, DenseRelation
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((8,), ("model",))
    rng = np.random.default_rng(0)

    # ---- blocked matmul: tiny budget forces the co-partition plan ----
    join = fra.Join(eq_pred((1, 0)), jproj(L(0), L(1), R(1)), MATMUL,
                    fra.scan("A", 2), fra.scan("B", 2))
    q = fra.Query(fra.Agg(project_key(0, 2), ADD, join), inputs=("A", "B"))
    a = jnp.asarray(rng.normal(size=(8, 8, 8, 8)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(8, 8, 8, 8)).astype(np.float32))
    env = {"A": DenseRelation(a, 2), "B": DenseRelation(b, 2)}

    eng = RAEngine(q)
    low = eng.lower(env)
    comp = low.compile(mesh=mesh, mem_budget=1.0)
    (plan,) = comp.plans.values()
    assert plan.kind == "copartition", plan.kind
    # planner-emitted in_shardings: contraction axes carry the mesh axis
    assert tuple(comp.input_specs["A"]) == (None, "model"), comp.input_specs
    assert tuple(comp.input_specs["B"]) == ("model", None), comp.input_specs

    out = comp(env)
    walks = eng.trace_count
    out2 = comp(env)
    assert eng.trace_count == walks, "re-lowered on second call"
    hlo = comp.lower_text()
    ref = low.eager(env)
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(ref.data),
                               rtol=1e-4, atol=1e-4)
    assert "all-reduce" in hlo or "reduce-scatter" in hlo, "no psum emitted"

    # ---- GCN gradient program under the same pipeline ----
    gjoin = fra.Join(eq_pred((0, 0)), jproj(L(1)), MUL,
                     fra.const("Edge", 2), fra.scan("Node", 1))
    gq = fra.Query(fra.Agg(identity_key(1), ADD, gjoin), inputs=("Node",))
    from repro.core.kernels import SQUARE, SUM_CHUNK
    from repro.core.keys import EMPTY_KEY, TRUE
    sq = fra.Select(TRUE, identity_key(1), SQUARE, gq.root)
    loss = fra.Agg(EMPTY_KEY, ADD,
                   fra.Select(TRUE, identity_key(1), SUM_CHUNK, sq))
    prog = ra_autodiff(fra.Query(loss, inputs=("Node",)))

    n, nnz, d = 16, 64, 8
    src = rng.integers(0, n, size=nnz); dst = rng.integers(0, n, size=nnz)
    genv = {
        "Edge": CooRelation(
            jnp.asarray(np.stack([src, dst], 1), jnp.int32),
            jnp.asarray(rng.normal(size=nnz).astype(np.float32)), (n, n)),
        "Node": DenseRelation(
            jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)), 1),
    }
    geng = RAEngine(prog)
    glow = geng.lower(genv)
    gcomp = glow.compile(mesh=mesh, mem_budget=1.0)
    assert gcomp.plans, "GCN join got no physical plan"
    out_s, grads_s = gcomp(genv)
    out_e, grads_e = glow.eager(genv)
    np.testing.assert_allclose(np.asarray(out_s.data), np.asarray(out_e.data),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(grads_s["Node"].data),
                               np.asarray(grads_e["Node"].data),
                               rtol=1e-4, atol=1e-4)
    print("ENGINE-SPMD-OK")
    """
)


@pytest.mark.spmd
def test_compiled_spmd_in_shardings():
    repo = pathlib.Path(__file__).resolve().parent.parent
    r = subprocess.run(
        [sys.executable, "-c", _SPMD_SCRIPT],
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
    assert "ENGINE-SPMD-OK" in r.stdout
