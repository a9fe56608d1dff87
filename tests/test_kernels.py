"""Per-kernel shape/dtype sweeps, interpret=True, allclose vs ref.py."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.matmul.ops import blocked_matmul
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.segsum.ops import segment_sum
from repro.kernels.segsum.ref import segment_sum_ref


@pytest.mark.parametrize(
    "m,k,n",
    [
        (128, 128, 128),
        (256, 128, 384),
        (128, 512, 128),
        (100, 70, 30),    # ragged -> exercises padding
        (1, 128, 128),
        (33, 257, 65),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blocked_matmul_matches_ref(m, k, n, dtype):
    rng = np.random.default_rng(hash((m, k, n)) % 2**31)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype=dtype)
    y = jnp.asarray(rng.normal(size=(k, n)), dtype=dtype)
    got = blocked_matmul(x, y, interpret=True)
    ref = matmul_ref(x, y)
    # f32 tolerance covers tiled-vs-monolithic accumulation-order drift.
    tol = 5e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("bm,bn,bk", [(128, 128, 128), (256, 128, 128)])
def test_blocked_matmul_tile_shapes(bm, bn, bk):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(256, 256)), dtype=jnp.float32)
    y = jnp.asarray(rng.normal(size=(256, 256)), dtype=jnp.float32)
    got = blocked_matmul(x, y, bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(matmul_ref(x, y)), rtol=5e-4, atol=5e-4
    )


@pytest.mark.parametrize(
    "e,d,s",
    [
        (512, 128, 128),
        (1000, 64, 100),   # ragged
        (512, 256, 256),
        (37, 16, 9),
    ],
)
def test_segment_sum_matches_ref(e, d, s):
    rng = np.random.default_rng(hash((e, d, s)) % 2**31)
    msg = jnp.asarray(rng.normal(size=(e, d)), dtype=jnp.float32)
    seg = jnp.asarray(rng.integers(0, s, size=e), dtype=jnp.int32)
    got = segment_sum(msg, seg, s, interpret=True)
    ref = segment_sum_ref(msg, seg, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)


def _ids(pattern, e, s, rng):
    """Segment ids for the sorted, tile-skipping Σ: the order the edges
    come in, hub segments, empty segment tiles, dropped ids."""
    if pattern == "unsorted":
        return rng.integers(0, s, size=e)
    if pattern == "sorted":
        return np.sort(rng.integers(0, s, size=e))
    if pattern == "hub":  # one segment's edges span several 512-edge blocks
        ids = rng.integers(0, s, size=e)
        ids[rng.random(e) < 0.8] = 200
        return ids
    if pattern == "empty_tiles":
        # of six 128-segment tiles only 1 and 3 hold edges: leading,
        # middle and trailing tiles are empty
        return rng.choice(np.r_[128:256, 384:512], size=e)
    if pattern == "dropped":  # COO padding (-1) and ids out of range
        ids = rng.integers(0, s, size=e)
        pick = rng.random(e)
        ids[pick < 0.2] = -1
        ids[(pick >= 0.2) & (pick < 0.3)] = s + rng.integers(0, 300)
        ids[(pick >= 0.3) & (pick < 0.35)] = -7
        return ids
    if pattern == "all_dropped":
        return np.full(e, -1)
    raise ValueError(pattern)


# E = 1,500 and 2,600 are not multiples of the 512-edge block, S = 700 not
# one of the 128-segment tile
@pytest.mark.parametrize(
    "pattern,e",
    [
        ("unsorted", 1500),
        ("sorted", 1500),
        ("hub", 2600),
        ("empty_tiles", 1500),
        ("dropped", 1500),
        ("all_dropped", 600),
    ],
)
def test_segment_sum_id_patterns_match_ref_fwd_and_grad(pattern, e):
    d, s = 24, 700
    rng = np.random.default_rng(len(pattern) * 1000 + e)
    msg = jnp.asarray(rng.normal(size=(e, d)), dtype=jnp.float32)
    seg = jnp.asarray(_ids(pattern, e, s, rng), dtype=jnp.int32)
    got = segment_sum(msg, seg, s, interpret=True)
    ref = segment_sum_ref(msg, seg, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-4)

    w = jnp.asarray(rng.normal(size=(s, d)), dtype=jnp.float32)
    grad = jax.grad(lambda m: jnp.sum(segment_sum(m, seg, s, interpret=True) * w))
    grad_ref = jax.grad(lambda m: jnp.sum(segment_sum_ref(m, seg, s) * w))
    np.testing.assert_allclose(
        np.asarray(grad(msg)), np.asarray(grad_ref(msg)), rtol=1e-6, atol=1e-6
    )


def test_segment_sum_dropped_ids_add_nothing_even_if_not_finite():
    """A message whose id is dropped (padding, out of range) adds nothing to
    any segment, as in jax.ops.segment_sum, even where it is inf or NaN."""
    rng = np.random.default_rng(3)
    e, d, s = 1300, 8, 300
    seg = _ids("dropped", e, s, rng)
    msg = rng.normal(size=(e, d)).astype(np.float32)
    bad = (seg < 0) | (seg >= s)
    msg[bad] = np.where(rng.random((int(bad.sum()), d)) < 0.5, np.inf, np.nan)
    got = segment_sum(jnp.asarray(msg), jnp.asarray(seg, jnp.int32), s, interpret=True)
    ref = segment_sum_ref(jnp.asarray(msg), jnp.asarray(seg, jnp.int32), s)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("visits_per_call", [3, 5, 11])
def test_segment_sum_split_into_launches_matches_ref(monkeypatch, visits_per_call):
    """A schedule longer than the SMEM budget runs as several launches; a
    tile cut by a launch boundary (the hub) carries its partial sum."""
    import repro.kernels.segsum.segsum as segsum_kernel

    rng = np.random.default_rng(visits_per_call)
    e, d, s = 2600, 16, 700
    msg = jnp.asarray(rng.normal(size=(e, d)), dtype=jnp.float32)
    seg = jnp.asarray(_ids("hub", e, s, rng), dtype=jnp.int32)
    monkeypatch.setattr(segsum_kernel, "VISITS_PER_CALL", visits_per_call)
    segment_sum.clear_cache()
    try:
        jaxpr = str(jax.make_jaxpr(
            lambda m, g: segment_sum(m, g, s, interpret=True))(msg, seg))
        # 6 edge blocks + 6 segment tiles = 12 visits
        assert jaxpr.count("pallas_call") == -(-12 // visits_per_call)
        got = segment_sum(msg, seg, s, interpret=True)
    finally:
        segment_sum.clear_cache()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(segment_sum_ref(msg, seg, s)), rtol=1e-5, atol=1e-4
    )


@pytest.mark.parametrize(
    "pattern,e,s",
    [
        ("unsorted", 1500, 700),
        ("hub", 2600, 700),
        ("empty_tiles", 1500, 700),
        ("dropped", 1500, 700),
        ("all_dropped", 600, 700),
        ("unsorted", 512, 128),
        ("unsorted", 7, 5),
    ],
)
def test_visit_schedule_visits_each_edge_block_of_a_tile_once(pattern, e, s):
    """The schedule, on numpy alone: E'/be + S'/bs visits, tiles in
    non-decreasing order, each (tile, block) pair that holds an edge
    visited once and adding, every empty tile visited once and adding
    nothing, the padding visits adding nothing."""
    from repro.kernels.segsum.ops import visit_schedule

    bs, be = 128, 512
    rng = np.random.default_rng(e + s)
    ids = _ids(pattern, e, s, rng)
    epad, spad = e + (-e) % be, s + (-s) % bs
    nb, nt = epad // be, spad // bs
    key = np.sort(np.where((ids >= 0) & (ids < s), ids, spad))
    key = np.pad(key, (0, epad - e), constant_values=spad)
    tile, block, valid = visit_schedule(key, nt, nb + nt, bs=bs, be=be, xp=np)
    assert len(tile) == len(block) == len(valid) == nb + nt
    assert (np.diff(tile) >= 0).all()
    assert ((0 <= tile) & (tile < nt)).all() and ((0 <= block) & (block < nb)).all()
    holding = {(int(k) // bs, p // be) for p, k in enumerate(key) if k < spad}
    adding = [(int(t), int(b)) for t, b, ok in zip(tile, block, valid) if ok]
    assert sorted(adding) == sorted(holding)
    assert len(set(adding)) == len(adding)
    empty = set(range(nt)) - {t for t, _ in holding}
    for t in empty:  # the padding visits repeat the last tile
        visits = (tile == t).sum()
        assert visits == 1 or (t == nt - 1 and visits > 1)
        assert not valid[tile == t].any()
    assert set(tile.tolist()) == set(range(nt))


def test_segment_sum_empty_segments():
    msg = jnp.ones((8, 4), dtype=jnp.float32)
    seg = jnp.zeros((8,), dtype=jnp.int32)  # all into segment 0
    got = segment_sum(msg, seg, 4, interpret=True)
    assert np.allclose(np.asarray(got)[0], 8.0)
    assert np.allclose(np.asarray(got)[1:], 0.0)


@pytest.mark.parametrize("ambient", ["default", "highest"])
def test_segment_sum_kernel_dot_takes_f32_passes(ambient):
    """The Σ kernel's one-hot dot asks for the f32 passes itself, at any
    ambient matmul precision: at the TPU default the MXU would round each
    message to bf16, and the Σ would no longer equal an f32 scatter-add."""
    with jax.default_matmul_precision(ambient):
        jaxpr = str(jax.make_jaxpr(
            lambda m, s: segment_sum(m, s, 8, interpret=True)
        )(jnp.ones((16, 4)), jnp.zeros((16,), jnp.int32)))
    dots = re.findall(r"dot_general\[(.*?)preferred_element_type", jaxpr, re.S)
    assert dots
    for params in dots:
        assert "precision=(Precision.HIGHEST, Precision.HIGHEST)" in params
