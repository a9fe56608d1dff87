"""Public wrapper for the segment-sum kernel: the ``pallas``/``interpret``
tiers of the engine's ``segment_sum`` dispatch op (core/kernels.py).

``segment_sum(msg, seg, num_segments)`` sorts the edges by segment id and
runs the MXU one-hot-matmul kernel (segsum.py) over a visit schedule that
takes each segment tile only to its own edge blocks; ``use_pallas=False``
short-circuits to the jnp oracle (ref.py). The wrapper's XLA ops, in
order:

- ids outside ``[0, num_segments)`` (the -1 COO padding among them) become
  a sentinel that sorts last and lies beyond every segment tile;
- a stable sort of the ids with their positions as payload;
- one ``take`` of the messages in that order, padded to the edge block
  (the only copy of the messages, as the pad it replaces was), with the
  rows past the kept ids zeroed in the one block where a dot reads them;
- the schedule (``visit_schedule``), from a ``searchsorted`` of the tile
  boundaries in the sorted ids.

The order of the edges is invisible in the result: the Σ is keyed by
segment.

The wrapper carries a ``jax.custom_vjp`` so reverse-mode AD differentiates
*through* the Pallas forward: the cotangent of ``msg`` is the gather
``g[seg]`` (out-of-range / padding ids contribute zero), matching the VJP
of ``jax.ops.segment_sum`` exactly — so a compiled training step may route
its forward Σ through the kernel and still be jax.grad-differentiable.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels import (
    AccumModel,
    BlockModel,
    GridModel,
    Interval,
    KernelContract,
)

from .ref import segment_sum_ref
from .segsum import launches, segment_sum_pallas


def visit_schedule(ids, num_tiles: int, num_visits: int, *, bs: int, be: int,
                   xp=jnp):
    """The kernel's visits over sorted ``ids`` (length a multiple of
    ``be``; ids at or past ``num_tiles·bs`` sort last and belong to no
    tile): ``(tile, block, valid)``, each of length ``num_visits``, which
    must be at least ``len(ids)/be + num_tiles``.

    Tile t holds the ids in ``[t·bs, (t+1)·bs)``, a range of the sorted
    ids, and is visited once for each edge block that range touches, in
    order; an empty tile once, adding nothing (its output is zeros). Tiles
    come in increasing order, so each tile's visits are consecutive. Two
    tiles share at most a boundary block, so the visits number at most
    ``len(ids)/be + num_tiles − 1``; the rest repeat the last visit and add
    nothing. ``xp`` is ``jnp`` in the wrapper, ``np`` in the contract's
    replay of a concrete site."""
    nb = ids.shape[0] // be
    bounds = xp.searchsorted(ids, xp.arange(num_tiles + 1) * bs, side="left")
    start, end = bounds[:-1], bounds[1:]
    first = xp.minimum(start // be, nb - 1)
    last = xp.maximum(first, (end - 1) // be)
    count = last - first + 1
    stop = xp.cumsum(count)
    v = xp.arange(num_visits)
    tile = xp.searchsorted(stop, v, side="right")
    pad = tile >= num_tiles
    tile = xp.minimum(tile, num_tiles - 1)
    block = xp.where(pad, last[-1], first[tile] + v - (stop[tile] - count[tile]))
    valid = ~pad & (end[tile] > start[tile])
    return (tile.astype(np.int32), block.astype(np.int32),
            valid.astype(np.int32))


def _sizes(e: int, num_segments: int, bs: int, be: int):
    """Padded edges, padded segments, and the schedule's padded length."""
    epad = e + (-e) % be
    spad = num_segments + (-num_segments) % bs
    n, per = launches(epad // be + spad // bs)
    return epad, spad, n * per


def _run(msg, seg, num_segments, bs, be, interpret, use_pallas):
    if not use_pallas:
        return segment_sum_ref(msg, seg, num_segments)
    e, d = msg.shape
    if e == 0 or num_segments == 0:
        return jnp.zeros((num_segments, d), msg.dtype)
    epad, spad, nv = _sizes(e, num_segments, bs, be)
    keep = (seg >= 0) & (seg < num_segments)
    ids, order = jax.lax.sort(
        (jnp.where(keep, seg, spad), jnp.arange(e, dtype=jnp.int32)),
        num_keys=1,
        is_stable=True,
    )
    ids = jnp.pad(ids, (0, epad - e), constant_values=spad)
    ordered = msg.at[jnp.pad(order, (0, epad - e))].get(
        mode="promise_in_bounds"
    )
    # The kept ids fill the first `n` places. A dot reads a place past them
    # only in the block that holds place n: zero those rows there, so that
    # a dropped message (even an inf or a NaN) adds nothing.
    n = jnp.sum(keep, dtype=jnp.int32)
    at = jnp.minimum(n // be, epad // be - 1) * be
    rows = jax.lax.dynamic_slice_in_dim(ordered, at, be)
    past = at + jnp.arange(be, dtype=jnp.int32) >= n
    ordered = jax.lax.dynamic_update_slice_in_dim(
        ordered, jnp.where(past[:, None], 0, rows), at, 0
    )
    tile, block, valid = visit_schedule(ids, spad // bs, nv, bs=bs, be=be)
    out = segment_sum_pallas(
        ordered, ids, tile, block, valid, spad, bs=bs, be=be, interpret=interpret
    )
    return out[:num_segments].astype(msg.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _segment_sum(msg, seg, num_segments, bs, be, interpret, use_pallas):
    return _run(msg, seg, num_segments, bs, be, interpret, use_pallas)


def _fwd(msg, seg, num_segments, bs, be, interpret, use_pallas):
    out = _run(msg, seg, num_segments, bs, be, interpret, use_pallas)
    return out, seg


def _bwd(num_segments, bs, be, interpret, use_pallas, seg, g):
    # out[s] = Σ_e 1[seg_e == s]·msg[e]  ⇒  ∂out/∂msg[e] = g[seg_e];
    # ids outside [0, num_segments) (the -1 padding) received no sum and
    # get a zero cotangent. Segment ids are integral: float0 tangent.
    valid = (seg >= 0) & (seg < num_segments)
    safe = jnp.clip(seg, 0, num_segments - 1)
    dmsg = jnp.where(valid[:, None], g[safe], jnp.zeros((), dtype=g.dtype))
    dseg = np.zeros(seg.shape, dtype=jax.dtypes.float0)
    return dmsg, dseg


_segment_sum.defvjp(_fwd, _bwd)


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "bs", "be", "interpret", "use_pallas"),
)
def segment_sum(
    msg: jnp.ndarray,
    seg: jnp.ndarray,
    num_segments: int,
    *,
    bs: int = 128,
    be: int = 512,
    interpret: bool,
    use_pallas: bool = True,
) -> jnp.ndarray:
    """Segment-sum of ``msg`` (E, D) by ``seg`` (E,) into ``num_segments``
    rows, on the Pallas one-hot-matmul kernel.

    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU);
    ``False`` compiles it for the TPU. ``bs``/``be`` are the segment/edge
    tile sizes (ragged inputs are padded up).
    Differentiable wrt ``msg`` (custom VJP: gather of the cotangent at
    ``seg``).
    """
    return _segment_sum(
        msg, seg.astype(jnp.int32), num_segments, bs, be, interpret, use_pallas
    )


# -- contract ----------------------------------------------------------------


def _grid_model(
    info: Dict[str, Any], seg: Optional[Any] = None, **concrete: Any
) -> Optional[GridModel]:
    """The visit grid ``_run`` produces for a dispatch site at the default
    tiles: E padded to ``be``-multiples, the segment count to
    ``bs``-multiples, one program per visit over the launches' schedules
    laid end to end (a tile cut by a launch boundary resumes from its
    carried partial sum, so it is one run of visits). Statically the
    visited tile and block are only known to lie in range (Intervals),
    and the accumulator is zeroed and stored once per run of a tile's
    visits; the sanitizer passes the concrete ``seg`` ids, whose schedule
    gives the exact indices, so it counts each output block's runs."""
    e, d = int(info["nnz"]), int(info["dim"])
    s = int(info["num_segments"])
    if e == 0 or s == 0 or d == 0:
        return None  # zero-nnz / zero-dim sites are guarded before dispatch
    bs, be = 128, 512
    epad, spad, nv = _sizes(e, s, bs, be)
    nb, nt = epad // be, spad // bs
    if seg is not None:
        ids = np.asarray(seg)
        ids = np.sort(np.where((ids >= 0) & (ids < s), ids, spad), kind="stable")
        ids = np.pad(ids, (0, epad - e), constant_values=spad)
        tile, block, _ = visit_schedule(ids, nt, nv, bs=bs, be=be, xp=np)

        def tile_of(v):
            return int(tile[v])

        def block_of(v):
            return int(block[v])
    else:
        def tile_of(v):
            return Interval(0, nt - 1)

        def block_of(v):
            return Interval(0, nb - 1)

    return GridModel(
        grid=(nv,),
        inputs=(
            BlockModel("seg", (1, epad), (1, be), lambda v: (0, block_of(v))),
            BlockModel("msg", (epad, d), (be, d), lambda v: (block_of(v), 0)),
            BlockModel("carry", (bs, d), (bs, d), lambda v: (0, 0)),
        ),
        output=BlockModel("out", (spad, d), (bs, d), lambda v: (tile_of(v), 0)),
        accumulator=AccumModel(axis=0, store="run"),
    )


#: the statically checkable contract of this package (docs/kernels.md;
#: proven by analysis.kernelcheck, cross-checked by the sanitizer tier).
CONTRACT = KernelContract(
    op="segment_sum",
    dtypes="floating",
    accum_dtype="float32",
    masking=(
        "segment ids outside [0, num_segments) (COO_PAD_KEY among them) "
        "sort last as a sentinel past every segment tile and match no "
        "one-hot row; their message rows are zeroed where a dot reads them",
        "edges padded to the `be` tile carry the sentinel",
        "padded segment rows [num_segments, S') are sliced off on return",
    ),
    vjp="gather g[seg] of the cotangent (inline jnp; padding ids get zero)",
    vjp_pairs=(),
    grid_model=_grid_model,
)
