"""MXU-tiled matmul kernel — the Σ⋈(MatMul) join-aggregate on TPU.

Grid (M/bm, N/bn, K/bk); the K axis is the innermost (fastest-varying)
grid dimension so the f32 VMEM accumulator for an (i, j) output tile stays
live across the contraction. Tiles default to 128×128×128: MXU-aligned
(the systolic array is 128×128) and small enough that
x-tile + y-tile + acc + out ≈ (128·128·4)·4 B ≈ 256 KiB ≪ 16 MiB VMEM,
leaving room for double-buffered pipelining of the HBM→VMEM copies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        y_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul_pallas(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """x @ y with explicit VMEM tiling. Shapes must tile evenly (the ops.py
    wrapper pads); dims should be multiples of 128 for MXU alignment."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    out_dtype = out_dtype or x.dtype
    nk = k // bk

    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=(m // bm, n // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="blocked_matmul",
    )(x, y)
