"""Public wrapper for the row-gather kernel: the ``pallas``/``interpret``
tiers of the engine's ``gather_join`` dispatch op (core/kernels.py).

``gather_rows(table, rows)`` masks invalid (negative / out-of-range) row
ids to zero rows — the COO pad-and-mask contract — and runs the
scalar-prefetch DMA kernel (gather.py); ``use_pallas=False``
short-circuits to the jnp oracle (ref.py).

The wrapper carries a ``jax.custom_vjp`` so reverse-mode AD differentiates
*through* the Pallas forward, and the gradient stays **in-tier**: the
cotangent of ``table`` is the scatter-add of ``g`` by ``rows`` — exactly
the segment-sum op — routed to the segsum kernel package under the same
``interpret``/``use_pallas`` flags as the forward. A compiled step that
gathers through the DMA kernel therefore back-propagates through the
matching one-hot-matmul scatter kernel, never silently falling back to a
different physical tier.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels import (
    BlockModel,
    GridModel,
    Interval,
    KernelContract,
    VjpPair,
)

from .gather import ROWS_PER_CALL, gather_rows_pallas
from .ref import gather_rows_ref


def _run(table, rows, num_rows, interpret, use_pallas):
    if not use_pallas:
        return gather_rows_ref(table, rows)
    if rows.shape[0] == 0:  # empty gather: zero-nnz COO guard
        return jnp.zeros((0, table.shape[1]), dtype=table.dtype)
    valid = (rows >= 0) & (rows < num_rows)
    safe = jnp.clip(rows, 0, max(num_rows - 1, 0)).astype(jnp.int32)
    out = gather_rows_pallas(table, safe, interpret=interpret)
    return jnp.where(valid[:, None], out, jnp.zeros((), table.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _gather_rows(table, rows, num_rows, interpret, use_pallas):
    return _run(table, rows, num_rows, interpret, use_pallas)


def _fwd(table, rows, num_rows, interpret, use_pallas):
    out = _run(table, rows, num_rows, interpret, use_pallas)
    return out, rows


def _bwd(num_rows, interpret, use_pallas, rows, g):
    # out[e] = table[rows_e]  ⇒  dtable = Σ_e 1[rows_e == r]·g[e] — the
    # scatter-add IS the segment-sum op; stay in the forward's tier so
    # gradients run the same physical kernels. Invalid (padding) ids are
    # dropped by the segsum kernels' out-of-range contract.
    if rows.shape[0] == 0:
        dtable = jnp.zeros((num_rows, g.shape[1]), dtype=g.dtype)
    elif use_pallas:
        from repro.kernels.segsum.ops import segment_sum

        dtable = segment_sum(
            g, rows, num_rows, interpret=interpret, use_pallas=True
        )
    else:
        from repro.kernels.segsum.ref import segment_sum_ref

        dtable = segment_sum_ref(g, rows, num_rows)
    drows = np.zeros(rows.shape, dtype=jax.dtypes.float0)
    return dtable, drows


_gather_rows.defvjp(_fwd, _bwd)


@functools.partial(
    jax.jit, static_argnames=("interpret", "use_pallas")
)
def _jitted(table, rows, interpret, use_pallas):
    return _gather_rows(table, rows, table.shape[0], interpret, use_pallas)


def gather_rows(
    table: jnp.ndarray,
    rows: jnp.ndarray,
    *,
    interpret: bool,
    use_pallas: bool = True,
) -> jnp.ndarray:
    """Gather rows of ``table`` (N, D) at ``rows`` (E,) on the Pallas
    scalar-prefetch DMA kernel; ids outside ``[0, N)`` (COO padding)
    produce zero rows. ``interpret=True`` runs the kernel in the Pallas
    interpreter (CPU); ``False`` compiles it for the TPU. Differentiable
    wrt ``table`` (custom VJP: same-tier segment-sum scatter of the
    cotangent)."""
    return _jitted(table, rows.astype(jnp.int32), interpret, use_pallas)


# -- contract ----------------------------------------------------------------


def _grid_model(
    info: Dict[str, Any], rows: Optional[Any] = None, **concrete: Any
) -> Optional[GridModel]:
    """The scalar-prefetch launch geometry: one program per output row,
    over the rows padded to a whole number of ``ROWS_PER_CALL`` launches
    (the union of the launches' grids). Statically the table block index
    is only known to lie in the clamp range ``[0, N)`` (an Interval); the
    sanitizer passes the concrete ``rows`` to sharpen it into the exact
    per-step DMA indices."""
    e, n, d = int(info["rows"]), int(info["num_rows"]), int(info["dim"])
    if e == 0 or n == 0 or d == 0:
        return None  # the zero-nnz guard short-circuits before the kernel
    e += (-e) % min(e, ROWS_PER_CALL)
    if rows is not None:
        import numpy as np_mod

        safe = np_mod.clip(np_mod.asarray(rows), 0, n - 1)
        safe = np_mod.pad(safe, (0, e - safe.shape[0]))

        def table_map(i):
            return (int(safe[i]), 0, 0)
    else:
        span = Interval(0, n - 1)

        def table_map(i):
            return (span, 0, 0)

    # the kernel's (rows, 1, D) views; the squeezed row dim is a block of 1
    return GridModel(
        grid=(e,),
        inputs=(BlockModel("table", (n, 1, d), (1, 1, d), table_map),),
        output=BlockModel("out", (e, 1, d), (1, 1, d), lambda i: (i, 0, 0)),
        accumulator=None,
    )


def _vjp_info(info: Dict[str, Any]) -> Dict[str, Any]:
    # dtable = Σ_e 1[rows_e == r]·g[e] — the segment-sum dispatch op
    return {
        "nnz": info["rows"],
        "dim": info["dim"],
        "num_segments": info["num_rows"],
        "dtype": info["dtype"],
    }


#: the statically checkable contract of this package (docs/kernels.md;
#: proven by analysis.kernelcheck, cross-checked by the sanitizer tier).
CONTRACT = KernelContract(
    op="gather_join",
    dtypes="floating",
    accum_dtype="none",
    masking=(
        "row ids outside [0, N) (COO padding) are clamped before the DMA "
        "and their output rows zeroed after it",
        "empty gathers (E = 0) short-circuit to zeros before the kernel",
    ),
    vjp="same-tier segment_sum scatter of the cotangent (dispatch op)",
    vjp_pairs=(VjpPair("segment_sum", _vjp_info),),
    grid_model=_grid_model,
)
