"""Extra integration coverage: SQL → planner pipeline, MoE expert-parallel
flag, serving consistency for sliding-window archs, and optimizer/config
plumbing added during §Perf work."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.core import compiler, fra
from repro.core.planner import input_pspecs, plan_query
from repro.core.relation import DenseRelation
from repro.core.sql import compile_sql
from repro.data import batch_for
from repro.models import build_model
from repro.train import make_train_step
from repro.train.trainer import init_train_state

# whole-stack integration runs: CI's default lane skips these (-m "not slow")
pytestmark = pytest.mark.slow


def test_sql_query_through_planner():
    """The paper's matmul SQL goes through the distribution planner: big
    relations co-partition, the gradient-side joins inherit specs."""
    q = compile_sql(
        "SELECT A.row, B.col, SUM(matrix_multiply(A.mat, B.mat)) "
        "FROM A, B WHERE A.col = B.row GROUP BY A.row, B.col",
        schema={"A": ("row", "col"), "B": ("row", "col")},
        inputs=("A", "B"),
    )
    env = {
        "A": jax.ShapeDtypeStruct((512, 512, 256, 256), jnp.float32),
        "B": jax.ShapeDtypeStruct((512, 512, 256, 256), jnp.float32),
    }
    plans = plan_query(q, env, n_devices=256)
    assert len(plans) == 1
    (plan,) = plans.values()
    assert plan.kind == "copartition"
    specs = input_pspecs(q, plans)
    assert set(specs) == {"A", "B"}


@pytest.mark.parametrize("arch", ["olmoe-1b-7b", "deepseek-v3-671b"])
def test_moe_shard_experts_flag_neutral_on_values(arch):
    """moe_shard_experts only adds sharding constraints — on a single
    device the logits must be bit-identical."""
    cfg = get_config(arch).reduced()
    rng = np.random.default_rng(0)
    batch = batch_for(cfg, 2, 16, rng)

    outs = []
    for flag in (False, True):
        model = build_model(replace(cfg, moe_shard_experts=flag))
        params = model.init(jax.random.PRNGKey(7))
        logits, _ = model.train_logits(params, batch)
        outs.append(np.asarray(logits, dtype=np.float32))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_remat_policy_dots_neutral_on_values():
    cfg = get_config("gemma2-9b").reduced()
    rng = np.random.default_rng(1)
    batch = batch_for(cfg, 2, 16, rng)
    losses = []
    for policy in ("nothing", "dots"):
        model = build_model(replace(cfg, remat=True, remat_policy=policy))
        state = init_train_state(model, jax.random.PRNGKey(8))
        step = make_train_step(model)
        _, _, m = step(state.params, state.opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[0] == pytest.approx(losses[1], rel=1e-6)


def test_gemma3_prefill_decode_consistency_sliding_window():
    """Sliding-window + global alternation: greedy continuation from the
    cache matches the full-sequence forward."""
    cfg = get_config("gemma3-4b").reduced()
    model = build_model(cfg)
    rng = np.random.default_rng(2)
    batch = batch_for(cfg, 1, 8, rng)
    params = model.init(jax.random.PRNGKey(9))

    logits_full, _ = model.train_logits(params, batch)
    lp, caches = model.prefill(params, batch, cache_len=16)
    np.testing.assert_allclose(
        np.asarray(lp[:, 0], np.float32),
        np.asarray(logits_full[:, -1], np.float32),
        rtol=2e-2, atol=2e-3,
    )


def test_ssm_pallas_flag_close_to_default():
    """The Pallas scan path (in the TPU interpreter on CPU) agrees with the
    XLA parallel-prefix path through the full falcon-mamba block stack."""
    from jax.experimental.pallas import tpu as pltpu

    cfg = get_config("falcon-mamba-7b").reduced()
    rng = np.random.default_rng(3)
    batch = batch_for(cfg, 1, 32, rng)
    m0 = build_model(replace(cfg, ssm_pallas=False))
    m1 = build_model(replace(cfg, ssm_pallas=True))
    params = m0.init(jax.random.PRNGKey(10))
    l0, _ = m0.train_logits(params, batch)
    with pltpu.force_tpu_interpret_mode():
        l1, _ = m1.train_logits(params, batch)
    np.testing.assert_allclose(
        np.asarray(l0, np.float32), np.asarray(l1, np.float32),
        rtol=1e-3, atol=1e-4,
    )
