"""Segment-sum kernel — the Σ-by-destination over an edge CooRelation.

This is the aggregation half of the GCN join-agg tree (paper §1/§6). A GPU
engine lowers it to atomic scatter-adds; the TPU has no efficient
random-access scatter, so we ADAPT the insight instead of porting it: the
scatter is re-expressed as a sequence of one-hot × message matmuls that run
on the 128×128 MXU.

  out[s, :]  =  Σ_e 1[seg_e == s] · msg[e, :]
             =  (one-hot(seg))ᵀ @ msg

The edges arrive sorted by segment id (the ops.py wrapper sorts them), so
segment tile i owns one contiguous range of edges and needs only the edge
blocks that range touches. The wrapper flattens those (tile, block) pairs
into a visit schedule, and the grid is one program per visit: the schedule
is scalar-prefetched into SMEM, the index maps steer the message and id
blocks to ``block[v]`` and the output block to ``tile[v]``, and each visit
builds a (bs, be) one-hot in VREGs and accumulates onehot @ msg_block into
a VMEM f32 accumulator. A tile's visits are consecutive: the accumulator
is zeroed at the first and stored at the last. The work is
O(E/be + S/bs) grid steps, against O(S·E/(bs·be)) for a sweep of every
edge block by every segment tile.

SMEM is small (1 MiB on a v5e chip), so a schedule longer than
``VISITS_PER_CALL`` runs as several launches over consecutive stretches of
it. A tile cut by a launch boundary carries its partial sum into the next
launch (the ``carry`` block), which then stores the whole sum over it in
the output they share (aliased from one launch to the next).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: visits per kernel launch (the schedule's three int32 arrays fill
#: 192 KiB of SMEM).
VISITS_PER_CALL = 16384


def launches(num_visits: int) -> tuple:
    """``(launches, visits per launch)`` for a schedule of at least
    ``num_visits`` visits: the fewest launches within the SMEM budget, of
    equal length (the schedule is padded up to their product)."""
    n = -(-num_visits // VISITS_PER_CALL)
    return n, -(-num_visits // n)


def _segsum_kernel(tile_ref, block_ref, valid_ref, seg_ref, msg_ref, carry_ref,
                   *refs, bs: int, nv: int):
    # refs: [the previous launch's output (aliased, never read),] the
    # output block, the f32 accumulator
    o_ref, acc_ref = refs[-2:]
    v = pl.program_id(0)
    t = tile_ref[v]
    first = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)
    last = (v == nv - 1) | (tile_ref[jnp.minimum(v + 1, nv - 1)] != t)

    @pl.when(v == 0)
    def _resume():
        # zeros, or the partial sum of a tile the previous launch began
        acc_ref[...] = carry_ref[...]

    @pl.when(first & (v > 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid_ref[v] != 0)
    def _accumulate():
        local = seg_ref[...] - t * bs  # (1, be) int32 ids of this edge block
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

    @pl.when(last)
    def _store():
        o_ref[...] = acc_ref[...]


def _launch(tile, block, valid, seg, msg, carry, prev, num_segments, *,
            bs, be, interpret):
    nv = tile.shape[0]
    d = msg.shape[1]
    in_specs = [
        # ids as a (1, be) row: a 1-D int32 block does not match the
        # TPU's 1-D layout, a row of a 2-D array does
        pl.BlockSpec((1, be), lambda v, t, b, ok: (0, b[v])),
        pl.BlockSpec((be, d), lambda v, t, b, ok: (b[v], 0)),
        pl.BlockSpec((bs, d), lambda v, t, b, ok: (0, 0)),
    ]
    args = [tile, block, valid, seg, msg, carry]
    if prev is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(prev)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nv,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bs, d), lambda v, t, b, ok: (t[v], 0)),
        scratch_shapes=[pltpu.VMEM((bs, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_segsum_kernel, bs=bs, nv=nv),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((num_segments, d), jnp.float32),
        # the output of the previous launch (argument 6, counting the
        # scalar-prefetch ones) is this launch's output buffer
        input_output_aliases={} if prev is None else {6: 0},
        interpret=interpret,
        name="segment_sum",
    )(*args)


def segment_sum_pallas(
    msg: jnp.ndarray,    # (E, D), rows in segment order
    seg: jnp.ndarray,    # (E,) int32, non-decreasing; ids ≥ num_segments drop
    tile: jnp.ndarray,   # (V,) int32 visit schedule: segment tile per visit,
    block: jnp.ndarray,  # (V,) int32   its edge block,
    valid: jnp.ndarray,  # (V,) int32   and 0 where the visit adds nothing
    num_segments: int,
    *,
    bs: int = 128,
    be: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """The f32 (num_segments, D) Σ of ``msg`` by ``seg`` over the visit
    schedule: one launch, or several of ``launches(V)[1]`` visits."""
    e, d = msg.shape
    assert seg.shape == (e,)
    assert e % be == 0 and num_segments % bs == 0, (e, be, num_segments, bs)
    n, per = launches(tile.shape[0])
    assert tile.shape[0] == n * per, (tile.shape, n, per)
    seg = seg[None, :]
    call = functools.partial(_launch, seg=seg, msg=msg,
                             num_segments=num_segments, bs=bs, be=be,
                             interpret=interpret)
    carry = jnp.zeros((bs, d), jnp.float32)
    out = None
    for c in range(n):
        part = slice(c * per, (c + 1) * per)
        if c:
            # a tile the previous launch left open resumes from its stored
            # partial sum
            t0 = tile[c * per]
            carry = jnp.where(
                t0 == tile[c * per - 1],
                jax.lax.dynamic_slice_in_dim(out, t0 * bs, bs),
                0.0,
            )
        out = call(tile[part], block[part], valid[part], carry=carry, prev=out)
    return out
