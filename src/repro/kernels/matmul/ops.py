"""Public wrapper for the blocked-matmul kernel: the ``pallas``/
``interpret`` tiers of the engine's ``blocked_matmul`` dispatch op
(core/kernels.py).

``blocked_matmul(x, y)`` pads both operands up to tile multiples and runs
the MXU-tiled kernel (matmul.py); ``use_pallas=False`` short-circuits to
the jnp oracle (ref.py). ``interpret=True`` runs the kernel in the Pallas
interpreter (CPU tests); ``False`` compiles it for the TPU.

The wrapper carries a ``jax.custom_vjp`` so reverse-mode AD differentiates
*through* the Pallas forward, and — matching the paper's Fig. 4 optimized
RJP kernels — the backward is two more blocked matmuls on the same tier:
``dX = g @ Yᵀ`` and ``dY = Xᵀ @ g``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.kernels import (
    AccumModel,
    BlockModel,
    GridModel,
    KernelContract,
    VjpPair,
)

from .matmul import matmul_pallas
from .ref import matmul_ref


def _pad_to(x: jnp.ndarray, m: int, axis: int) -> jnp.ndarray:
    r = x.shape[axis] % m
    if r == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, m - r)
    return jnp.pad(x, pad)


def _run(x, y, bm, bn, bk, interpret, use_pallas):
    if not use_pallas:
        return matmul_ref(x, y)
    m, n = x.shape[0], y.shape[1]
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    yp = _pad_to(_pad_to(y, bk, 0), bn, 1)
    out = matmul_pallas(xp, yp, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _blocked_matmul(x, y, bm, bn, bk, interpret, use_pallas):
    return _run(x, y, bm, bn, bk, interpret, use_pallas)


def _fwd(x, y, bm, bn, bk, interpret, use_pallas):
    return _run(x, y, bm, bn, bk, interpret, use_pallas), (x, y)


def _bwd(bm, bn, bk, interpret, use_pallas, res, g):
    x, y = res
    # Fig. 4 RJP kernels, routed through the same tier as the forward.
    dx = _run(g, y.T, bm, bn, bk, interpret, use_pallas)
    dy = _run(x.T, g, bm, bn, bk, interpret, use_pallas)
    return dx.astype(x.dtype), dy.astype(y.dtype)


_blocked_matmul.defvjp(_fwd, _bwd)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret", "use_pallas")
)
def blocked_matmul(
    x: jnp.ndarray,
    y: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool,
    use_pallas: bool = True,
) -> jnp.ndarray:
    """``x @ y`` via the MXU-tiled Pallas kernel, padding to tile
    multiples. ``bm``/``bn``/``bk`` are the output-row/output-col/
    contraction tile sizes. Differentiable (custom VJP: two blocked
    matmuls on the same tier)."""
    return _blocked_matmul(x, y, bm, bn, bk, interpret, use_pallas)


# -- contract ----------------------------------------------------------------


def _grid_model(info: Dict[str, Any], **concrete: Any) -> Optional[GridModel]:
    """The launch geometry ``_run`` produces at the default 128³ tiles:
    both operands padded to tile multiples, contraction sweep innermost."""
    m, k, n = int(info["m"]), int(info["k"]), int(info["n"])
    bm = bn = bk = 128
    mp = m + (-m) % bm
    kp = k + (-k) % bk
    np_ = n + (-n) % bn
    if 0 in (mp, kp, np_):
        return None  # degenerate extent: nothing is launched
    return GridModel(
        grid=(mp // bm, np_ // bn, kp // bk),
        inputs=(
            BlockModel("x", (mp, kp), (bm, bk), lambda i, j, kk: (i, kk)),
            BlockModel("y", (kp, np_), (bk, bn), lambda i, j, kk: (kk, j)),
        ),
        output=BlockModel("out", (mp, np_), (bm, bn), lambda i, j, kk: (i, j)),
        accumulator=AccumModel(axis=2, init_at=0, store="last"),
    )


def _vjp_dx_info(info: Dict[str, Any]) -> Dict[str, Any]:
    # dX = g @ Yᵀ: (m, n) @ (n, k)
    return {"m": info["m"], "k": info["n"], "n": info["k"], "dtype": info["dtype"]}


def _vjp_dy_info(info: Dict[str, Any]) -> Dict[str, Any]:
    # dY = Xᵀ @ g: (k, m) @ (m, n)
    return {"m": info["k"], "k": info["m"], "n": info["n"], "dtype": info["dtype"]}


#: the statically checkable contract of this package (docs/kernels.md;
#: proven by analysis.kernelcheck, cross-checked by the sanitizer tier).
CONTRACT = KernelContract(
    op="blocked_matmul",
    dtypes="floating",
    accum_dtype="float32",
    masking=(
        "operands zero-padded to 128-multiples; padded rows/cols multiply "
        "to zero and the output is sliced back to (m, n)",
    ),
    vjp="two same-tier blocked matmuls: dX = g @ Yᵀ, dY = Xᵀ @ g (Fig. 4)",
    vjp_pairs=(
        VjpPair("blocked_matmul", _vjp_dx_info),
        VjpPair("blocked_matmul", _vjp_dy_info),
    ),
    grid_model=_grid_model,
)
