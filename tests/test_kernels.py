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
