"""Segment-sum kernel — the Σ-by-destination over an edge CooRelation.

This is the aggregation half of the GCN join-agg tree (paper §1/§6). A GPU
engine lowers it to atomic scatter-adds; the TPU has no efficient
random-access scatter, so we ADAPT the insight instead of porting it: the
scatter is re-expressed as a sequence of one-hot × message matmuls that run
on the 128×128 MXU.

  out[s, :]  =  Σ_e 1[seg_e == s] · msg[e, :]
             =  (one-hot(seg))ᵀ @ msg

Grid (num_segments/bs, E/be): for each segment tile s we sweep the edge
tiles (innermost axis) building a (bs, be) one-hot in VREGs and
accumulating onehot @ msg_tile into a VMEM f32 accumulator. Cost is
O(S·E/(bs·be)) MXU issues — dense in E per segment tile, which on TPU
beats serialized scatter for the degree distributions of the paper's
graphs; edges need no sorting at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _segsum_kernel(seg_ref, msg_ref, o_ref, acc_ref, *, bs: int, ne: int):
    # Grid is (segment tile i, feature tile j, edge tile k) with the edge
    # sweep innermost so the (bs, bd) accumulator stays live across it.
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i = pl.program_id(0)
    local = seg_ref[...] - i * bs  # (1, be) int32 ids of this edge tile
    onehot = (
        local == jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
    ).astype(jnp.float32)  # (bs, be)
    # A Σ is a sum, not a product the caller asked for: at the default
    # precision the MXU would round each message to bf16, so the dot
    # always takes the f32 passes (the one-hot itself is exact in bf16).
    acc_ref[...] += jnp.dot(
        onehot, msg_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(pl.program_id(2) == ne - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def segment_sum_pallas(
    msg: jnp.ndarray,   # (E, D)
    seg: jnp.ndarray,   # (1, E) int32 in [0, num_segments) (pad with -1)
    num_segments: int,
    *,
    bs: int = 128,
    be: int = 512,
    bd: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    e, d = msg.shape
    assert seg.shape == (1, e)
    assert e % be == 0 and num_segments % bs == 0, (e, be, num_segments, bs)
    bd = bd or d
    assert d % bd == 0
    ne = e // be

    return pl.pallas_call(
        functools.partial(_segsum_kernel, bs=bs, ne=ne),
        grid=(num_segments // bs, d // bd, ne),
        in_specs=[
            # ids as a (1, be) row: a 1-D int32 block does not match the
            # TPU's 1-D layout, a row of a 2-D array does
            pl.BlockSpec((1, be), lambda i, j, k: (0, k)),
            pl.BlockSpec((be, bd), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bs, bd), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((num_segments, d), msg.dtype),
        scratch_shapes=[pltpu.VMEM((bs, bd), jnp.float32)],
        interpret=interpret,
        name="segment_sum",
    )(seg, msg)
