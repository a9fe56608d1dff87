"""Distribution planner: broadcast-vs-copartition decisions from relation
sizes + per-node memory budget (the paper's §1 optimizer claim) + an
8-device SPMD execution test run in a subprocess (device count must be set
before JAX initializes)."""

import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fra
from repro.core.kernels import ADD, MATMUL
from repro.core.keys import L, R, eq_pred, jproj, project_key
from repro.core.planner import input_pspecs, plan_join, plan_query
from repro.core.relation import DenseRelation


def matmul_join(left_leaf, right_leaf):
    return fra.Join(
        eq_pred((1, 0)),                  # A.col == B.row
        jproj(L(0), L(1), R(1)),          # paper: ⟨keyL[0], keyL[1], keyR[1]⟩
        MATMUL,
        left_leaf,
        right_leaf,
    )


def matmul_query(left="A", right="B"):
    join = matmul_join(fra.scan(left, 2), fra.scan(right, 2))
    return fra.Query(
        fra.Agg(project_key(0, 2), ADD, join), inputs=(left, right)
    )


def _sds(shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def test_broadcast_small_side_chosen():
    """A small model matrix joined against a huge data matrix — the paper's
    data-parallel plan: broadcast the small side."""
    q = matmul_query()
    env = {
        "A": _sds((512, 512, 256, 256)),   # ~64 GB: must stay partitioned
        "B": _sds((512, 1, 256, 64)),      # ~32 MB: broadcastable
    }
    plans = plan_query(q, env, n_devices=16)
    (plan,) = plans.values()
    assert plan.kind == "broadcast_right"
    assert not plan.needs_psum
    # big side stays sharded on its non-contraction output dim (row),
    # small side replicated
    assert plan.left_shard_dim == 0
    assert plan.right_shard_dim is None
    assert "broadcast_left" not in plan.costs  # A exceeds the budget


def test_copartition_chosen_when_nothing_fits():
    """Two huge matrices, neither replicable within the per-node memory
    budget — the paper's tensor-parallel plan: co-partition on the join
    key, pay the output all-reduce."""
    q = matmul_query()
    env = {
        "A": _sds((512, 512, 256, 256)),   # ~64 GB each
        "B": _sds((512, 512, 256, 256)),
    }
    plans = plan_query(q, env, n_devices=16)
    (plan,) = plans.values()
    assert plan.kind == "copartition"
    assert plan.needs_psum
    # sharded on the contraction dims: A.col (dim 1), B.row (dim 0)
    assert plan.left_shard_dim == 1
    assert plan.right_shard_dim == 0
    assert set(plan.costs) == {"copartition"}


def test_cheapest_bytes_moved_wins_when_all_feasible():
    """When everything fits, the decision is by bytes moved — broadcasting
    the smaller side beats the 2×output all-reduce."""
    join = matmul_join(fra.scan("A", 2), fra.scan("B", 2))
    p = plan_join(join, 1e6, 4e6, 4e6, 16)
    assert p.kind == "broadcast_left"
    # co-partition was considered but costs 2·out > left gather
    assert p.costs["copartition"] > p.costs["broadcast_left"]


def test_memory_budget_flips_plan():
    """Exactly the paper's story: same relations, smaller nodes →
    the optimizer switches from broadcast to co-partition."""
    join = matmul_join(fra.scan("A", 2), fra.scan("B", 2))
    roomy = plan_join(join, 1e8, 1e9, 1e9, 16, mem_budget=8e9)
    tight = plan_join(join, 1e8, 1e9, 1e9, 16, mem_budget=1e7)
    assert roomy.kind == "broadcast_left"
    assert tight.kind == "copartition"


def test_plan_pspecs():
    q = matmul_query()
    env = {"A": _sds((512, 512, 256, 256)), "B": _sds((512, 512, 256, 256))}
    plans = plan_query(q, env, n_devices=16)
    specs = input_pspecs(q, plans)
    from jax.sharding import PartitionSpec as P

    assert specs["A"] == P(None, "model")
    assert specs["B"] == P("model", None)


_SPMD_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import compiler, fra
    from repro.core.kernels import ADD, MATMUL
    from repro.core.keys import L, R, eq_pred, jproj, project_key
    from repro.core.planner import input_pspecs, plan_query
    from repro.core.relation import DenseRelation
    from repro.launch.mesh import auto_mesh

    join = fra.Join(eq_pred((1, 0)), jproj(L(0), L(1), R(1)), MATMUL,
                    fra.scan("A", 2), fra.scan("B", 2))
    q = fra.Query(fra.Agg(project_key(0, 2), ADD, join), inputs=("A", "B"))

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(8, 8, 16, 16)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(8, 8, 16, 16)).astype(np.float32))
    env = {"A": DenseRelation(a, 2), "B": DenseRelation(b, 2)}

    # tiny budget forces the co-partition (tensor-parallel) plan
    plans = plan_query(q, env, n_devices=8, mem_budget=1.0)
    (plan,) = plans.values()
    assert plan.kind == "copartition", plan.kind

    mesh = auto_mesh((8,), ("model",))
    specs = input_pspecs(q, plans)
    a_sh = jax.device_put(a, NamedSharding(mesh, specs["A"]))
    b_sh = jax.device_put(b, NamedSharding(mesh, specs["B"]))

    @jax.jit
    def run(a, b):
        return compiler.execute(
            q.root, {"A": DenseRelation(a, 2), "B": DenseRelation(b, 2)}
        ).data

    # NamedShardings carry the mesh; no global mesh context needed
    out = run(a_sh, b_sh)
    hlo = jax.jit(run).lower(a_sh, b_sh).compile().as_text()

    ref = run(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # the co-partition plan must have produced a contraction all-reduce
    assert "all-reduce" in hlo or "reduce-scatter" in hlo, "no psum emitted"
    print("SPMD-OK")
    """
)


@pytest.mark.spmd
def test_copartition_executes_under_spmd():
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
    assert "SPMD-OK" in r.stdout
