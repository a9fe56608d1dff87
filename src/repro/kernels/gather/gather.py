"""Row-gather kernel — the edge ⋈ node side of the GCN join-aggregate.

The COO gather join reads, per edge, one row of a dense relation
(``out[e, :] = table[rows[e], :]``). A GPU engine lowers this to a plain
random-access gather; on TPU the idiomatic lowering is a **scalar-prefetch
DMA pipeline**: the row ids are scalar-prefetched so the BlockSpec
index_map can schedule one HBM→VMEM row copy per grid step, and Pallas
double-buffers the copies against the (trivial) compute.

Rows must be pre-clamped to ``[0, num_rows)`` — masking of invalid
(padding) ids happens in the ops.py wrapper, keeping the kernel a pure
copy. The grid is one program per output row. The table and the output
are viewed as ``(rows, 1, D)`` with the row dim squeezed out of the
block, so each block is a ``(1, D)`` slab spanning whole trailing dims:
the TPU compiler refuses a ``(1, D)`` block of a 2-D array, which is not
(8, 128)-aligned. The row ids are scalar-prefetched into SMEM, which is
small (1 MiB on a v5e chip), so a long gather runs as a ``lax.map`` over
launches of at most ``ROWS_PER_CALL`` rows each.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: rows gathered per kernel launch (their int32 ids fill 256 KiB of SMEM).
ROWS_PER_CALL = 65536


def _gather_kernel(rows_ref, table_ref, o_ref):
    # The index_map already steered this program's table block to row
    # rows[i]; the body is the VMEM copy the DMA pipeline overlaps.
    del rows_ref
    o_ref[...] = table_ref[...]


def gather_rows_pallas(
    table: jnp.ndarray,  # (N, D)
    rows: jnp.ndarray,   # (E,) int32 in [0, N)
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    e, = rows.shape
    n, d = table.shape
    chunk = min(e, ROWS_PER_CALL)
    # pad ids are row 0 (in range); their output rows are sliced off
    rows = jnp.pad(rows, (0, (-e) % chunk))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(chunk,),
        in_specs=[
            pl.BlockSpec((None, 1, d), lambda i, rows_ref: (rows_ref[i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, d), lambda i, rows_ref: (i, 0, 0)),
    )
    call = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((chunk, 1, d), table.dtype),
        interpret=interpret,
        name="gather_join",
    )
    table3 = table.reshape(n, 1, d)
    out = jax.lax.map(lambda r: call(r, table3), rows.reshape(-1, chunk))
    return out.reshape(-1, d)[:e]
