"""Out-of-core chunked execution: the differential harness.

Every test here runs the same step twice — once in core (the oracle) and
once through a ``Database(memory_budget=...)`` session small enough to
force chunk-wave streaming — and asserts the results agree to 1e-5:

  * dense logistic regression (the paper's §2.3 SQL program): the data
    matrix streams, the labels co-stream with the same row boundaries,
    the parameters stay resident (gradient Σ-accumulated across waves);
  * a GCN conv step over an owner-partitioned COO edge relation: edge
    waves touch O(1) segment blocks, the padded last chunk rides the
    pad-and-mask contract;
  * a KGE-style bilinear score (two joins against the entity table).

Plus the control surfaces: budgets forcing 1/2/8-wave execution,
budget-too-small and unstreamable-query error paths, bit-identity with
an unconstrained budget, and the serving batch cache over a budgeted
session.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro
from repro.core import fra
from repro.core.chunkstore import ChunkStore, OutOfCoreError
from repro.core.engine import StreamedCompiled
from repro.core.kernels import (
    ADD, EXP, MUL, SQERR, SUM_CHUNK,
)
from repro.core.keys import (
    EMPTY_KEY, TRUE, L, eq_pred, identity_key, jproj,
)
from repro.core.planner import plan_waves, _rel_bytes
from repro.core.relation import COO_PAD_KEY, CooRelation, DenseRelation
from repro.relational.gcn import gcn_square_loss, partitioned_edges

ATOL = 1e-5

requires8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 devices (tier1-oocore lane: "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)

LOGREG_SQL = """
mm   := SELECT Rx.row, SUM(multiply(Rx.val, theta.val))
        FROM Rx, theta WHERE Rx.col = theta.col GROUP BY Rx.row;
pred := SELECT mm.row, logistic(mm.val) FROM mm;
SELECT SUM(xent(pred.val, Ry.val)) FROM pred, Ry WHERE pred.row = Ry.row
"""


# ---------------------------------------------------------------------------
# model builders: (db-filler, query, wrt) triples shared by all sweeps
# ---------------------------------------------------------------------------


def _logreg_fill(db, n=64, m=8, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    y = jnp.asarray(
        (rng.uniform(size=n) > 0.5).astype(np.float32) * 0.98 + 0.01
    )
    theta = jnp.asarray(rng.normal(size=m) * 0.1, jnp.float32)
    db.put("Rx", X, keys=("row", "col"))
    db.put("Ry", y, keys=("row",))
    db.put("theta", theta, keys=("col",))
    return db


def _logreg_handle(db):
    return db.sql(LOGREG_SQL, wrt=("theta", "Rx", "Ry"))


def _logreg_bytes(n=64, m=8):
    return n * m * 4 + n * 4 + m * 4


def _gcn_fill(db, n=60, e=500, d=8, seed=1, shards=4):
    rng = np.random.default_rng(seed)
    edge = partitioned_edges(
        np.stack([rng.integers(0, n, e), rng.integers(0, n, e)], 1),
        rng.normal(size=e).astype(np.float32),
        n,
        shards,
    )
    node = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    db.put("Edge", edge)
    db.put("Node", node, keys=("node",))
    return db


def _kge_query():
    # DistMult-flavoured bilinear score over triples (h, t) with weight w:
    #   loss = Σ_t Σ_d [ (Σ_h w_ht · Ent[h]) ⊙ Ent[t] ]_d
    conv = fra.Agg(
        identity_key(1), ADD,
        fra.Join(
            eq_pred((0, 0)), jproj(L(1)), MUL,
            fra.scan("Triple", 2), fra.scan("Ent", 1),
        ),
    )
    pair = fra.Join(
        eq_pred((0, 0)), jproj(L(0)), MUL, conv, fra.scan("Ent", 1)
    )
    sc = fra.Select(TRUE, identity_key(1), SUM_CHUNK, pair)
    return fra.Query(
        fra.Agg(EMPTY_KEY, ADD, sc), inputs=("Triple", "Ent")
    )


def _kge_fill(db, n=40, e=300, d=6, seed=3, partition=True):
    rng = np.random.default_rng(seed)
    keys = np.stack([rng.integers(0, n, e), rng.integers(0, n, e)], 1)
    vals = (rng.normal(size=e) * 0.3).astype(np.float32)
    if partition:
        triple = partitioned_edges(keys, vals, n, 4)
    else:
        triple = CooRelation(
            jnp.asarray(keys, jnp.int32), jnp.asarray(vals), (n, n)
        )
    db.put("Triple", triple)
    db.put("Ent", jnp.asarray(rng.normal(size=(n, d)), jnp.float32),
           keys=("ent",))
    return db


def _grad_close(g0, g1):
    assert set(g0) == set(g1)
    for name in g0:
        a, b = g0[name], g1[name]
        if isinstance(a, CooRelation):
            np.testing.assert_array_equal(
                np.asarray(a.keys), np.asarray(b.keys)
            )
            np.testing.assert_allclose(
                np.asarray(a.values), np.asarray(b.values), atol=ATOL
            )
        else:
            np.testing.assert_allclose(
                np.asarray(a.data), np.asarray(b.data), atol=ATOL
            )


# ---------------------------------------------------------------------------
# the differential harness: chunked ≡ in-core across models and budgets
# ---------------------------------------------------------------------------


def test_logreg_chunked_matches_incore_across_wave_counts():
    l0, g0 = _logreg_handle(_logreg_fill(repro.Database())).step()
    total = _logreg_bytes()
    # budgets sized so resident θ + the moving set needs 2 / 8 waves
    for budget, want_waves in [(total * 0.7, 2), (total * 0.15, 8)]:
        db = _logreg_fill(repro.Database(memory_budget=budget))
        h = _logreg_handle(db)
        l1, g1 = h.step()
        assert isinstance(h.last, StreamedCompiled)
        assert h.last.num_waves == want_waves
        np.testing.assert_allclose(
            np.asarray(l0.data), np.asarray(l1.data), atol=ATOL
        )
        _grad_close(g0, g1)
        # the data matrix streamed and the labels co-streamed with it
        assert h.last.plan.stream == "Rx"
        assert h.last.plan.co_streams == ("Ry",)
        st = db.counters()["spill"]
        assert st["spilled_relations"] == 2
        assert st["fetched_chunks"] == 2 * want_waves


def test_gcn_chunked_matches_incore():
    n = 60
    db0 = _gcn_fill(repro.Database(), n=n)
    l0, g0 = db0.query(gcn_square_loss(n)).step(wrt=("Edge", "Node"))
    total = _rel_bytes(db0.get("Edge")) + _rel_bytes(db0.get("Node"))
    db = _gcn_fill(repro.Database(memory_budget=total / 3), n=n)
    h = db.query(gcn_square_loss(n))
    l1, g1 = h.step(wrt=("Edge", "Node"))
    assert isinstance(h.last, StreamedCompiled)
    assert h.last.num_waves >= 2
    assert h.last.plan.owner_aligned  # owner-partitioned edge waves
    np.testing.assert_allclose(
        np.asarray(l0.data), np.asarray(l1.data), atol=ATOL
    )
    _grad_close(g0, g1)


@pytest.mark.parametrize("partition", [True, False])
def test_kge_chunked_matches_incore(partition):
    db0 = _kge_fill(repro.Database(), partition=partition)
    l0, g0 = db0.query(_kge_query()).step(wrt=("Triple", "Ent"))
    total = _rel_bytes(db0.get("Triple")) + _rel_bytes(db0.get("Ent"))
    db = _kge_fill(
        repro.Database(memory_budget=total / 2.5), partition=partition
    )
    h = db.query(_kge_query())
    l1, g1 = h.step(wrt=("Triple", "Ent"))
    assert isinstance(h.last, StreamedCompiled)
    assert h.last.num_waves >= 2
    np.testing.assert_allclose(
        np.asarray(l0.data), np.asarray(l1.data), atol=ATOL
    )
    _grad_close(g0, g1)


def test_forward_only_query_streams_too():
    n = 60
    db0 = _gcn_fill(repro.Database(), n=n)
    out0 = db0.query(gcn_square_loss(n)).forward()
    total = _rel_bytes(db0.get("Edge")) + _rel_bytes(db0.get("Node"))
    db = _gcn_fill(repro.Database(memory_budget=total / 3), n=n)
    h = db.query(gcn_square_loss(n))
    out1 = h.forward()
    assert isinstance(h.last, StreamedCompiled)
    np.testing.assert_allclose(
        np.asarray(out0.data), np.asarray(out1.data), atol=ATOL
    )


# ---------------------------------------------------------------------------
# bit-identity with no / unconstraining budget (the in-core fast path)
# ---------------------------------------------------------------------------


def test_unconstrained_budget_is_bit_identical_to_no_budget():
    db0 = _logreg_fill(repro.Database())
    h0 = _logreg_handle(db0)
    l0, g0 = h0.step()
    # a budget everything fits under: plan_waves returns None, the
    # session takes the exact pre-existing path (same plans, same bits)
    db1 = _logreg_fill(repro.Database(memory_budget=1 << 30))
    h1 = _logreg_handle(db1)
    l1, g1 = h1.step()
    assert not isinstance(h1.last, StreamedCompiled)
    # node ids differ between independently-built handles; the chosen
    # physical plans must not
    assert sorted(p.kind for p in h1.last.plans.values()) == sorted(
        p.kind for p in h0.last.plans.values()
    )
    np.testing.assert_array_equal(np.asarray(l0.data), np.asarray(l1.data))
    for name in g0:
        np.testing.assert_array_equal(
            np.asarray(g0[name].data), np.asarray(g1[name].data)
        )
    assert db1.counters()["spill"] == {
        "spilled_relations": 0, "spilled_bytes": 0,
        "fetched_chunks": 0, "fetched_bytes": 0,
    }


# ---------------------------------------------------------------------------
# error paths: too-small budgets and unstreamable queries
# ---------------------------------------------------------------------------


def test_budget_smaller_than_resident_raises():
    db = _gcn_fill(repro.Database(memory_budget=64.0))
    # resident Node alone exceeds 64 bytes: no wave count can help
    with pytest.raises(OutOfCoreError, match="too small"):
        db.query(gcn_square_loss(60)).step(wrt=("Node",))


def test_budget_needing_more_waves_than_rows_raises():
    # resident θ holds 8 of the 18 bytes: 10 B of headroom needs more
    # waves than Rx has rows
    db = _logreg_fill(repro.Database(memory_budget=18.0), n=16, m=2)
    with pytest.raises(OutOfCoreError, match="waves|too small"):
        _logreg_handle(db).step()


def test_donation_under_streaming_raises():
    total = _logreg_bytes()
    db = _logreg_fill(repro.Database(memory_budget=total * 0.5))
    with pytest.raises(OutOfCoreError, match="donate"):
        _logreg_handle(db).step(donate=("theta",))


def test_unstreamable_query_names_the_offending_node():
    # exp is neither linear nor zero-preserving: a Σ-partial passing
    # through it cannot merge additively across waves
    n = 32
    sq = fra.Agg(
        EMPTY_KEY, ADD,
        fra.Select(TRUE, identity_key(1), SUM_CHUNK, fra.scan("X", 1)),
    )
    bad = fra.Select(TRUE, identity_key(0), EXP, sq)
    q = fra.Query(bad, inputs=("X",))
    rng = np.random.default_rng(0)
    db = repro.Database(memory_budget=n * 4 * 8 * 0.5)
    db.put("X", jnp.asarray(rng.normal(size=(n, 8)), jnp.float32),
           keys=("i",))
    with pytest.raises(OutOfCoreError, match="exp"):
        db.query(q).forward()


# ---------------------------------------------------------------------------
# chunk store mechanics
# ---------------------------------------------------------------------------


def test_chunkstore_spill_fetch_counters_and_idempotence():
    rng = np.random.default_rng(5)
    rel = DenseRelation(jnp.asarray(rng.normal(size=(12, 3)), jnp.float32), 1)
    store = ChunkStore()
    mani = store.spill("A", rel, 3)
    assert mani.num_chunks == 3 and "A" in store
    assert store.stats["spilled_relations"] == 1
    spilled = store.stats["spilled_bytes"]
    assert spilled == 12 * 3 * 4
    # same manifest again: a no-op, counters unchanged
    store.spill("A", rel, mani)
    assert store.stats["spilled_bytes"] == spilled
    parts = [store.fetch("A", w) for w in range(3)]
    assert store.stats["fetched_chunks"] == 3
    assert store.stats["fetched_bytes"] == spilled
    np.testing.assert_array_equal(
        np.concatenate([np.asarray(p.data) for p in parts]),
        np.asarray(rel.data),
    )
    store.drop("A")
    assert "A" not in store and store.stats["spilled_bytes"] == 0


def test_plan_waves_none_without_budget_or_pressure():
    db = _logreg_fill(repro.Database())
    env = {n: db.get(n) for n in ("Rx", "Ry", "theta")}
    q = _logreg_handle(db).query
    assert plan_waves(q, env, None) is None
    assert plan_waves(q, env, 1e12) is None
    wp = plan_waves(q, env, _logreg_bytes() * 0.5)
    assert wp is not None and wp.num_waves >= 2
    assert wp.streamed_names == ("Rx", "Ry")


# ---------------------------------------------------------------------------
# serving over a budgeted session
# ---------------------------------------------------------------------------


class _StubModel:
    cfg = None

    def prefill(self, params, batch, cache_len):
        t = batch["tokens"]
        return t[..., None].astype(jnp.float32) * params, {"len": cache_len}


def test_bucketed_prefill_warmup_with_spilled_relations():
    from repro.serving import BucketedPrefill

    total = _logreg_bytes()
    db = _logreg_fill(repro.Database(memory_budget=total * 0.5))
    # a training step spills + streams through the same session…
    _logreg_handle(db).step()
    assert db.counters()["spill"]["spilled_relations"] == 2
    # …and the serving cache on top of it behaves exactly as unbudgeted:
    # warmup compiles per bucket, repeats hit, the counters match
    srv = BucketedPrefill(
        _StubModel(), cache_len=16, db=db, buckets=[(2, 8), (4, 16)]
    )
    srv.warmup(jnp.asarray(2.0))
    assert db.counters()["cache"] == {"hits": 0, "misses": 2, "evictions": 0}
    logits, _ = srv.prefill(
        jnp.asarray(2.0), {"tokens": jnp.ones((1, 8), jnp.int32)}
    )
    assert logits.shape == (1, 8, 1)
    c = db.counters()  # one tree: serving cache next to spill stats
    assert c["cache"] == {"hits": 1, "misses": 2, "evictions": 0}
    assert c["spill"]["spilled_relations"] == 2


@pytest.mark.spmd
@requires8
def test_budgeted_session_never_silently_replicates():
    """Regression: with committed layouts on the 4×2 host mesh, a
    budgeted (but fitting) session reuses the recorded plan with zero
    silently-moved bytes, exactly like an unbudgeted one."""
    import warnings

    from repro.core.engine import ReshardWarning
    from repro.launch.mesh import make_host_mesh
    from repro.launch.sharding import catalog_shardings

    db = _logreg_fill(repro.Database(memory_budget=1 << 30), n=64, m=8)
    db.use_mesh(make_host_mesh(model=2))
    handle = db.sql(LOGREG_SQL, wrt=("theta",))
    loss1, _ = handle.step()
    placed = catalog_shardings(db)
    for name, sh in placed.items():
        db.put(name, jax.device_put(db.get(name).data, sh))
    with warnings.catch_warnings():
        warnings.simplefilter("error", ReshardWarning)
        loss2, _ = handle.step()
    assert handle.last.counters["reshard"]["last_call_bytes"] == 0
    assert handle.last.counters["reshard"]["bytes_moved"] == 0
    np.testing.assert_allclose(
        np.asarray(loss1.data), np.asarray(loss2.data), atol=ATOL
    )


@pytest.mark.spmd
@requires8
def test_gcn_4x_budget_waves_on_host_mesh():
    """The acceptance gate: a GCN grad step whose COO edge relation is
    ≥4× the device-memory budget completes via chunk waves on the 4×2
    host mesh and matches the in-core oracle."""
    from repro.launch.mesh import make_host_mesh

    n, e, d = 200, 4000, 16
    db0 = _gcn_fill(repro.Database(), n=n, e=e, d=d, shards=8)
    l0, g0 = db0.query(gcn_square_loss(n)).step(wrt=("Edge", "Node"))

    edge_bytes = _rel_bytes(db0.get("Edge"))
    node_bytes = _rel_bytes(db0.get("Node"))
    budget = node_bytes + edge_bytes / 4  # edge ≥ 4× its headroom
    assert edge_bytes >= 4 * (budget - node_bytes)
    db = _gcn_fill(
        repro.Database(mesh=make_host_mesh(model=2), memory_budget=budget),
        n=n, e=e, d=d, shards=8,
    )
    h = db.query(gcn_square_loss(n))
    l1, g1 = h.step(wrt=("Edge", "Node"))
    assert isinstance(h.last, StreamedCompiled)
    assert h.last.num_waves >= 4
    np.testing.assert_allclose(
        np.asarray(l0.data), np.asarray(l1.data), atol=ATOL
    )
    _grad_close(g0, g1)


def test_const_data_relations_stream_when_only_params_are_wrt():
    """The SQL front door lowers non-``wrt`` relations to Const leaves;
    the wave planner must still stream them — differentiating only the
    params while streaming the constant design matrix is the canonical
    budgeted workload."""
    db0 = _logreg_fill(repro.Database())
    h0 = db0.sql(LOGREG_SQL, wrt=("theta",))
    l0, g0 = h0.step()
    db = _logreg_fill(repro.Database(memory_budget=_logreg_bytes() * 0.5))
    h = db.sql(LOGREG_SQL, wrt=("theta",))
    l1, g1 = h.step()
    assert isinstance(h.last, StreamedCompiled)
    assert h.last.plan.stream == "Rx"      # a Const leaf, not a TableScan
    assert h.last.plan.co_streams == ("Ry",)
    np.testing.assert_allclose(
        np.asarray(l0.data), np.asarray(l1.data), atol=ATOL
    )
    _grad_close(g0, g1)


# ---------------------------------------------------------------------------
# static wave certification (repro.analysis.certify) — the oocore lane
# asserts the certifier's independent re-derivation of plan_waves
# ---------------------------------------------------------------------------


def test_streamed_logreg_plan_certifies():
    """The certifier re-derives wave soundness for a streamed plan:
    boundary coverage, budget sizing, and grad derivability — proven off
    the plan record, not observed from an execution."""
    from repro.analysis import certify

    db = _logreg_fill(repro.Database(memory_budget=_logreg_bytes() * 0.7))
    env = {n: db.get(n) for n in ("Rx", "Ry", "theta")}  # before spill
    h = _logreg_handle(db)
    h.step()
    assert isinstance(h.last, StreamedCompiled)
    cert = certify(h.last, env, query=h.query, wrt=("theta",))
    assert cert.kind == "streamed"
    assert cert.waves["boundaries_ok"] and cert.waves["budget_ok"]
    assert cert.waves["num_waves"] == h.last.num_waves == 2
    assert cert.waves["max_wave_bytes"] <= cert.waves["budget"]
    assert cert.ok
    assert cert.grad is not None and cert.grad["full_rjp"]
    assert "waves: ok" in cert.render()


def test_streamed_gcn_plan_certifies_owner_alignment():
    """Owner-partitioned COO streams certify end to end: the wave cuts
    never straddle an owner run, and the edge relation's shard offsets
    are consistent with its owner column."""
    from repro.analysis import certify

    n = 60
    db0 = _gcn_fill(repro.Database(), n=n)
    total = _rel_bytes(db0.get("Edge")) + _rel_bytes(db0.get("Node"))
    db = _gcn_fill(repro.Database(memory_budget=total / 3), n=n)
    env = {"Edge": db.get("Edge"), "Node": db.get("Node")}
    h = db.query(gcn_square_loss(n))
    h.step(wrt=("Edge", "Node"))
    assert h.last.plan.owner_aligned
    cert = certify(h.last, env)
    assert cert.ok
    assert cert.waves["owner_aligned_ok"]
    assert cert.coo["relations"]["Edge"]["ok"]


def test_wave_certifier_rejects_tampered_plans():
    """Negative control: the certifier is an independent checker, so a
    corrupted plan record must fail it — non-covering boundaries, a cut
    through an owner run, and an over-budget wave count all flag."""
    import dataclasses
    from types import SimpleNamespace

    from repro.analysis.certify import _certify_waves

    db = _logreg_fill(repro.Database(memory_budget=_logreg_bytes() * 0.7))
    env = {n: db.get(n) for n in ("Rx", "Ry", "theta")}
    h = _logreg_handle(db)
    h.step()
    plan = h.last.plan
    assert _certify_waves(h.last, env)["ok"]  # sanity: genuine plan passes

    short = dataclasses.replace(plan, boundaries=plan.boundaries[:-1] + (63,))
    assert not _certify_waves(SimpleNamespace(plan=short), env)["boundaries_ok"]

    crowded = dataclasses.replace(plan, num_waves=1, boundaries=(0, 64))
    assert not _certify_waves(SimpleNamespace(plan=crowded), env)["budget_ok"]

    # owner-run straddle: a GCN edge plan with a cut moved off its
    # owner-aligned snap point
    n = 60
    db0 = _gcn_fill(repro.Database(), n=n)
    total = _rel_bytes(db0.get("Edge")) + _rel_bytes(db0.get("Node"))
    db2 = _gcn_fill(repro.Database(memory_budget=total / 3), n=n)
    env2 = {"Edge": db2.get("Edge"), "Node": db2.get("Node")}
    h2 = db2.query(gcn_square_loss(n))
    h2.step(wrt=("Edge", "Node"))
    plan2 = h2.last.plan
    owners = np.asarray(env2["Edge"].keys)[:, env2["Edge"].owner_dim]
    cut = None
    for c in range(1, owners.shape[0] - 1):
        if owners[c - 1] == owners[c] != COO_PAD_KEY:
            cut = c
            break
    assert cut is not None
    bad = dataclasses.replace(
        plan2, boundaries=(0, cut, int(owners.shape[0])), num_waves=2
    )
    res = _certify_waves(SimpleNamespace(plan=bad), env2)
    assert not res["owner_aligned_ok"]
