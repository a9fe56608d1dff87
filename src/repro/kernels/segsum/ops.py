"""Public wrapper for the segment-sum kernel: the ``pallas``/``interpret``
tiers of the engine's ``segment_sum`` dispatch op (core/kernels.py).

``segment_sum(msg, seg, num_segments)`` pads E and the segment count to
tile multiples (padding edges carry id -1, dropped by the one-hot) and
runs the MXU one-hot-matmul kernel (segsum.py); ``use_pallas=False``
short-circuits to the jnp oracle (ref.py).

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
    KernelContract,
)

from .ref import segment_sum_ref
from .segsum import segment_sum_pallas


def _run(msg, seg, num_segments, bs, be, bd, interpret, use_pallas):
    if not use_pallas:
        return segment_sum_ref(msg, seg, num_segments)
    e, d = msg.shape
    ep = (-e) % be
    if ep:
        msg = jnp.pad(msg, ((0, ep), (0, 0)))
        seg = jnp.pad(seg, (0, ep), constant_values=-1)
    sp = (-num_segments) % bs
    out = segment_sum_pallas(
        msg,
        seg.astype(jnp.int32)[None, :],
        num_segments + sp,
        bs=bs,
        be=be,
        bd=bd,
        interpret=interpret,
    )
    return out[:num_segments]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _segment_sum(msg, seg, num_segments, bs, be, bd, interpret, use_pallas):
    return _run(msg, seg, num_segments, bs, be, bd, interpret, use_pallas)


def _fwd(msg, seg, num_segments, bs, be, bd, interpret, use_pallas):
    out = _run(msg, seg, num_segments, bs, be, bd, interpret, use_pallas)
    return out, seg


def _bwd(num_segments, bs, be, bd, interpret, use_pallas, seg, g):
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
    static_argnames=("num_segments", "bs", "be", "bd", "interpret", "use_pallas"),
)
def segment_sum(
    msg: jnp.ndarray,
    seg: jnp.ndarray,
    num_segments: int,
    *,
    bs: int = 128,
    be: int = 512,
    bd: int | None = None,
    interpret: bool,
    use_pallas: bool = True,
) -> jnp.ndarray:
    """Segment-sum of ``msg`` (E, D) by ``seg`` (E,) into ``num_segments``
    rows, on the Pallas one-hot-matmul kernel.

    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU);
    ``False`` compiles it for the TPU. ``bs``/``be``/``bd`` are the
    segment/edge/feature tile sizes (ragged inputs are padded up).
    Differentiable wrt ``msg`` (custom VJP: gather of the cotangent at
    ``seg``).
    """
    return _segment_sum(
        msg, seg.astype(jnp.int32), num_segments, bs, be, bd, interpret, use_pallas
    )


# -- contract ----------------------------------------------------------------


def _grid_model(info: Dict[str, Any], **concrete: Any) -> Optional[GridModel]:
    """The launch geometry ``_run`` produces for a dispatch site at the
    default tiles: E padded to ``be``-multiples (pad ids -1), the segment
    count padded to ``bs``-multiples, edge sweep innermost."""
    e, d = int(info["nnz"]), int(info["dim"])
    s = int(info["num_segments"])
    bs, be, bd = 128, 512, d
    epad = e + (-e) % be
    spad = s + (-s) % bs
    if epad == 0 or spad == 0 or d == 0:
        return None  # zero-nnz / zero-dim sites are guarded before dispatch
    return GridModel(
        grid=(spad // bs, d // bd, epad // be),
        inputs=(
            BlockModel("seg", (1, epad), (1, be), lambda i, j, k: (0, k)),
            BlockModel("msg", (epad, d), (be, bd), lambda i, j, k: (k, j)),
        ),
        output=BlockModel("out", (spad, d), (bs, bd), lambda i, j, k: (i, j)),
        accumulator=AccumModel(axis=2, init_at=0, store="last"),
    )


#: the statically checkable contract of this package (docs/kernels.md;
#: proven by analysis.kernelcheck, cross-checked by the sanitizer tier).
CONTRACT = KernelContract(
    op="segment_sum",
    dtypes="floating",
    accum_dtype="float32",
    masking=(
        "edges padded to the `be` tile carry segment id -1 (COO_PAD_KEY) "
        "and match no one-hot row",
        "segment ids outside [0, num_segments) contribute to no output row",
        "padded segment rows [num_segments, S') are sliced off on return",
    ),
    vjp="gather g[seg] of the cotangent (inline jnp; padding ids get zero)",
    vjp_pairs=(),
    grid_model=_grid_model,
)
