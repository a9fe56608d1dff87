"""Kernel contract certification (``repro.analysis.kernelcheck``) and
the sanitizer dispatch tier.

Three layers: golden-file diagnostics for seeded contract violations
(racy grid, OOB index map, unpaired VJP, dtype-domain — stable rendered
reports, reviewed like any behavior change; regenerate with
``REGEN_GOLDEN=1``), the acceptance bar (the real registry certifies
clean; a seeded racy BlockSpec / OOB index map is *rejected* through
``certify_kernels`` with node-path diagnostics at the plan's actual
dispatch sites; a stateful predicate is caught by the resolution
replay), and the dynamic twin (the sanitizer tier raises
``SanitizerError`` whose ``kind`` matches the static verdict, and agrees
with the jnp tier end-to-end through the engine, forward and gradient).
"""

import dataclasses
import os
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import certify_kernels, certify_registry
from repro.analysis import kernelcheck
from repro.analysis.diagnostics import CheckReport
from repro.core import kernels as K
from repro.core.autodiff import ra_autodiff
from repro.core.engine import RAEngine
from repro.core.kernels import (
    AccumModel,
    BlockModel,
    GridModel,
    KernelContract,
    SanitizerError,
    VjpPair,
)
from repro.core.relation import CooRelation, DenseRelation
from repro.relational.gcn import gcn_square_loss

GOLDEN = Path(__file__).parent / "golden" / "kernelcheck"

F32 = jnp.dtype("float32")
I32 = jnp.dtype("int32")


# ---------------------------------------------------------------------------
# Seeded contract violations (shared by goldens, acceptance, sanitizer)
# ---------------------------------------------------------------------------

SEG_INFO = {"nnz": 512, "dim": 128, "num_segments": 128, "dtype": F32}


def _racy_grid_model(info, **concrete):
    """Output map ignores a non-reduction axis and there is no
    accumulator: every output block is stored grid[1] times."""
    return GridModel(
        grid=(2, 2),
        inputs=(BlockModel("msg", (256, 128), (128, 128), lambda i, j: (j, 0)),),
        output=BlockModel("out", (256, 128), (128, 128), lambda i, j: (i, 0)),
        accumulator=None,
    )


def _oob_grid_model(info, **concrete):
    """Input index map walks one block past the (padded) array."""
    return GridModel(
        grid=(2,),
        inputs=(BlockModel("msg", (256, 128), (128, 128), lambda i: (i + 1, 0)),),
        output=BlockModel("out", (256, 128), (128, 128), lambda i: (i, 0)),
        accumulator=None,
    )


def _contract_with(grid_model, **overrides):
    base = K.kernel_contract("segment_sum")
    return dataclasses.replace(base, grid_model=grid_model, **overrides)


# ---------------------------------------------------------------------------
# Golden-file diagnostics
# ---------------------------------------------------------------------------


def case_racy_grid():
    diags = kernelcheck.check_contract_grid(
        "segment_sum", _contract_with(_racy_grid_model), [SEG_INFO]
    )
    return CheckReport(tuple(diags))


def case_oob_index_map():
    diags = kernelcheck.check_contract_grid(
        "segment_sum", _contract_with(_oob_grid_model), [SEG_INFO]
    )
    return CheckReport(tuple(diags))


def case_unpaired_vjp():
    impl = K.KernelImpl(
        "segment_sum", "pallas", lambda *a: None, ("tpu",), 0, K._is_float
    )
    contract = _contract_with(
        K.kernel_contract("segment_sum").grid_model,
        vjp_pairs=(VjpPair("scatter_add", lambda info: dict(info)),),
    )
    return CheckReport(tuple(kernelcheck.check_impl(impl, contract, [SEG_INFO])))


def case_dtype_domain():
    # a hardware-tier impl with no floating predicate admits int32
    impl = K.KernelImpl(
        "segment_sum", "interpret", lambda *a: None, (), 0, None
    )
    info = {"nnz": 1024, "dim": 64, "num_segments": 256, "dtype": I32}
    contract = K.kernel_contract("segment_sum")
    return CheckReport(tuple(kernelcheck.check_impl(impl, contract, [info])))


CASES = {
    name[len("case_"):]: fn
    for name, fn in sorted(globals().items())
    if name.startswith("case_")
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_golden(name):
    report = CASES[name]()
    got = report.render() + "\n"
    path = GOLDEN / f"{name}.txt"
    if os.environ.get("REGEN_GOLDEN"):
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(got)
    assert path.exists(), f"golden file missing; REGEN_GOLDEN=1 to create: {path}"
    assert got == path.read_text()


def test_every_seeded_case_is_an_error_with_a_node_path():
    for name, fn in CASES.items():
        report = fn()
        assert report.errors, name
        assert all(d.node_path for d in report.diagnostics), name


# ---------------------------------------------------------------------------
# The acceptance bar: real registry clean, seeded violations rejected
# ---------------------------------------------------------------------------


def test_registry_certifies_clean():
    report = certify_registry()
    assert report.ok, report.render()
    assert report.render() == "ok (no diagnostics)"


def test_cli_exits_clean():
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.kernelcheck"],
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "kernelcheck:" in proc.stdout and "ok" in proc.stdout


def _gcn_prog_env():
    """COO conv: exercises gather_join + segment_sum sites, fwd + grad."""
    prog = ra_autodiff(gcn_square_loss(const_edges=True))
    rng = np.random.default_rng(7)
    n, nnz, d = 16, 40, 8
    env = {
        "Edge": CooRelation(
            jnp.asarray(
                np.stack(
                    [rng.integers(0, n, nnz), rng.integers(0, n, nnz)], 1
                ),
                jnp.int32,
            ),
            jnp.asarray(rng.normal(size=nnz), jnp.float32),
            (n, n),
        ),
        "Node": DenseRelation(
            jnp.asarray(rng.normal(size=(n, d)), jnp.float32), 1
        ),
    }
    return prog, env


def test_certified_plan_reports_clean_kernels():
    prog, env = _gcn_prog_env()
    low = RAEngine(prog).lower(env)
    report = certify_kernels(low)
    assert getattr(low.resolutions, "sites", ()), "no dispatch site recorded"
    assert report.ok, report.render()
    # cached on the Lowered: the second call is the same object
    assert certify_kernels(low) is report


@pytest.mark.parametrize(
    "bad_model,code",
    [(_racy_grid_model, "grid-race"), (_oob_grid_model, "grid-oob-index")],
)
def test_seeded_bad_blockspec_rejected_at_dispatch_sites(
    monkeypatch, bad_model, code
):
    """A racy / out-of-bounds BlockSpec in the segsum contract is
    statically rejected at the plan's actual dispatch sites."""
    import repro.kernels.segsum.ops as segsum_ops

    prog, env = _gcn_prog_env()
    low = RAEngine(prog).lower(env)
    monkeypatch.setattr(
        segsum_ops, "CONTRACT", _contract_with(bad_model)
    )
    report = certify_kernels(low, recheck=True)
    assert not report.ok
    hits = [d for d in report.errors if d.code == code]
    assert hits, report.render()
    assert all(d.node_path.startswith("dispatch:segment_sum[") for d in hits)


def test_stateful_predicate_rejected(monkeypatch):
    """The retrace-desync hazard, now a named diagnostic: a predicate
    that answers differently on replay flips the resolved tier between
    lowering and retrace — certify_kernels replays every recorded site
    and reports ``flappy-predicate``."""
    state = {"accept": True}

    def stateful(info):
        return state["accept"]  # reads mutable state, not the site info

    # on cpu the real pallas impl is backend-gated out, so this is the
    # only eligible pallas entry: rejecting on replay falls to jnp
    impl = K.register_impl(
        "segment_sum", "pallas", K._IMPLS[("segment_sum", "ref")][0].fn,
        priority=10, predicate=stateful,
    )
    try:
        prog, env = _gcn_prog_env()
        low = RAEngine(prog).lower(env, dispatch=("pallas", "jnp"))
        state["accept"] = False  # the state drifts before the retrace
        report = certify_kernels(low, recheck=True)
    finally:
        K._IMPLS[("segment_sum", "pallas")].remove(impl)
    flappy = [d for d in report.errors if d.code == "flappy-predicate"]
    assert flappy, report.render()
    assert any(d.node_path.startswith("dispatch:") for d in flappy)


# ---------------------------------------------------------------------------
# Sanitizer tier: dynamic twin of the static certifier
# ---------------------------------------------------------------------------


def test_sanitizer_agrees_with_static_verdict(monkeypatch):
    """On the same seeded-bad contract, the sanitizer raises the exact
    code the static certifier reports."""
    import repro.kernels.segsum.ops as segsum_ops

    rng = np.random.default_rng(0)
    msg = jnp.asarray(rng.normal(size=(512, 128)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, 128, 512), jnp.int32)
    for bad_model in (_racy_grid_model, _oob_grid_model):
        contract = _contract_with(bad_model)
        monkeypatch.setattr(segsum_ops, "CONTRACT", contract)
        static = kernelcheck.check_contract_grid(
            "segment_sum", contract, [SEG_INFO]
        )
        with pytest.raises(SanitizerError) as exc:
            K._segsum_sanitizer(msg, seg, 128)
        assert exc.value.kind == static[0].code
    monkeypatch.undo()
    # dtype-domain dynamically (direct call bypasses the float predicate)
    with pytest.raises(SanitizerError) as exc:
        K._segsum_sanitizer(jnp.ones((8, 4), jnp.int32), seg[:8], 5)
    assert exc.value.kind == "dtype-domain"


@pytest.mark.parametrize(
    "info", kernelcheck.default_shape_classes("segment_sum")[:3],
    ids=["tile-exact", "ragged", "one-tile"],
)
def test_segment_sum_contract_is_a_visit_grid_that_certifies(info):
    """The Σ's contract describes its visit grid: one program per visit,
    E'/512 + S'/128 of them, a run accumulator, and tiles and blocks in
    range statically; the certifier finds nothing at the shape classes."""
    model = K.kernel_contract("segment_sum").grid_model(dict(info))
    e, s = info["nnz"], info["num_segments"]
    assert model.grid == (-(-e // 512) + -(-s // 128),)
    assert model.accumulator == AccumModel(axis=0, store="run")
    out = model.output.index_map(0)
    assert isinstance(out[0], K.Interval) and out[0].hi == -(-s // 128) - 1
    assert K.simulate_grid(model) == []


def test_segment_sum_sanitizer_replays_the_concrete_schedule():
    """Given the ids, the contract's grid is the schedule the kernel runs:
    exact tile and block indices, one store per tile, every tile stored."""
    rng = np.random.default_rng(5)
    e, s = 1800, 400
    ids = rng.integers(-1, s + 5, e)
    ids[rng.random(e) < 0.5] = 3  # a hub over several edge blocks
    info = {"nnz": e, "dim": 8, "num_segments": s, "dtype": F32}
    model = K.kernel_contract("segment_sum").grid_model(info, seg=ids)
    tiles = [model.output.index_map(v)[0] for v in range(model.grid[0])]
    assert all(isinstance(t, int) for t in tiles)
    assert tiles == sorted(tiles) and set(tiles) == set(range(4))
    blocks = [model.inputs[1].index_map(v)[0] for v in range(model.grid[0])]
    assert all(isinstance(b, int) and 0 <= b < 4 for b in blocks)
    assert K.simulate_grid(model) == []


def _run_model(tiles, num_tiles):
    return GridModel(
        grid=(len(tiles),),
        inputs=(BlockModel("msg", (512, 8), (512, 8), lambda v: (0, 0)),),
        output=BlockModel(
            "out", (128 * num_tiles, 8), (128, 8), lambda v: (tiles[v], 0)
        ),
        accumulator=AccumModel(axis=0, store="run"),
    )


@pytest.mark.parametrize(
    "tiles,num_tiles,code",
    [([0, 0, 1, 0], 2, "grid-race"), ([0, 2, 2], 3, "grid-uncovered")],
    ids=["tile-revisited", "tile-skipped"],
)
def test_run_accumulator_rejects_a_broken_schedule(tiles, num_tiles, code):
    """A schedule that leaves a tile and comes back stores it twice; one
    that skips a tile never stores it."""
    kinds = [kind for kind, _ in K.simulate_grid(_run_model(tiles, num_tiles))]
    assert kinds == [code]
    assert K.simulate_grid(_run_model([0, 0, 1, 2, 2], 3)) == []


def test_sanitizer_clean_sites_match_ref_oracle():
    from repro.kernels.gather.ref import gather_rows_ref
    from repro.kernels.segsum.ref import segment_sum_ref

    rng = np.random.default_rng(1)
    msg = jnp.asarray(rng.normal(size=(100, 24)), jnp.float32)
    seg = jnp.asarray(rng.integers(-1, 30, 100), jnp.int32)  # pad ids too
    np.testing.assert_allclose(
        np.asarray(K._segsum_sanitizer(msg, seg, 30)),
        np.asarray(segment_sum_ref(msg, seg, 30)),
        atol=1e-5,
    )
    table = jnp.asarray(rng.normal(size=(30, 24)), jnp.float32)
    rows = jnp.asarray(rng.integers(-1, 31, 64), jnp.int32)  # invalid rows
    np.testing.assert_allclose(
        np.asarray(K._gather_sanitizer(table, rows)),
        np.asarray(gather_rows_ref(table, rows)),
        atol=1e-5,
    )


def test_sanitizer_tier_smoke_segsum_gather_fwd_grad():
    """The fast-lane smoke: segsum + gather_join forward/grad through the
    engine under the sanitizer tier agree with the jnp tier."""
    prog, env = _gcn_prog_env()
    eng = RAEngine(prog)
    out_j, grads_j = eng.lower(env, dispatch="jnp").compile()(env)
    out_s, grads_s = eng.lower(env, dispatch="sanitizer").compile()(env)
    np.testing.assert_allclose(
        np.asarray(out_s.data), np.asarray(out_j.data), rtol=1e-5, atol=1e-5
    )
    for name in grads_j:
        gj, gs = grads_j[name], grads_s[name]
        lj = gj.values if isinstance(gj, CooRelation) else gj.data
        ls = gs.values if isinstance(gs, CooRelation) else gs.data
        np.testing.assert_allclose(
            np.asarray(ls), np.asarray(lj), rtol=1e-5, atol=1e-5
        )
    low = eng.lower(env, dispatch="sanitizer")
    assert certify_kernels(low).ok
    recorded = {rec.tier for rec in low.resolutions.sites}
    assert recorded == {"sanitizer"}


# ---------------------------------------------------------------------------
# Property: certified-clean shape classes agree with the ref oracle
# ---------------------------------------------------------------------------


def _certify_and_run(nnz, dim, num_segments, seed):
    info = {"nnz": nnz, "dim": dim, "num_segments": num_segments, "dtype": F32}
    diags = kernelcheck.check_contract_grid(
        "segment_sum", K.kernel_contract("segment_sum"), [info]
    )
    assert diags == [], [d.render() for d in diags]
    from repro.kernels.segsum.ref import segment_sum_ref

    rng = np.random.default_rng(seed)
    msg = jnp.asarray(rng.normal(size=(nnz, dim)), jnp.float32)
    seg = jnp.asarray(rng.integers(-1, num_segments, nnz), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(K._segsum_sanitizer(msg, seg, num_segments)),
        np.asarray(segment_sum_ref(msg, seg, num_segments)),
        atol=1e-5,
    )


def test_random_shape_classes_certify_clean_and_match_oracle():
    """Seeded-random fallback for environments without hypothesis."""
    rng = np.random.default_rng(42)
    for trial in range(20):
        nnz = int(rng.integers(1, 1500))
        dim = int(rng.integers(1, 160))
        num_segments = int(rng.integers(1, 400))
        _certify_and_run(nnz, dim, num_segments, seed=trial)


def test_hypothesis_shape_classes_certify_clean_and_match_oracle():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=30, deadline=None)
    @given(
        nnz=st.integers(1, 2000),
        dim=st.integers(1, 200),
        num_segments=st.integers(1, 500),
    )
    def prop(nnz, dim, num_segments):
        _certify_and_run(nnz, dim, num_segments, seed=nnz * 31 + dim)

    prop()
