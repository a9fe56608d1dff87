"""Selective-scan kernel — the Mamba recurrence h_t = a_t ⊙ h_{t-1} + b_t.

XLA's best lowering (jax.lax.associative_scan) is a log-depth parallel
prefix: ~2·log₂(S) full passes over the (B,S,C,N) state tensors through
HBM. The CUDA kernels the SSM papers ship instead keep the running state
in SRAM and stream the sequence once. We ADAPT that insight to the TPU
memory hierarchy: the TPU grid executes sequentially, so a VMEM scratch
accumulator carries h across *time-tile* grid steps — giving exactly one
HBM read of (a, b) and one write of h (3 passes total vs ~2·log₂S ≈ 24
for S = 4 k), with the recurrence itself running in VREGs over a
(bt, bc·N) block.

Grid: (B, C/bc, S/bt), time innermost (sequential on TPU). Scratch: the
(bc, N) running state, persisting across time tiles of the same (B, C)
program; re-zeroed when a new (batch, channel-block) starts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(a_ref, b_ref, h_ref, state, *, bt: int):
    # a_ref/b_ref/h_ref blocks: (1, bt, bc, N); state: (bc, N) f32
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    # index the refs, not loaded values: a dynamic slice of a loaded
    # value lowers to dynamic_slice, which the TPU compiler does not take
    def step(t, h):
        h = a_ref[0, t].astype(jnp.float32) * h + b_ref[0, t].astype(jnp.float32)
        h_ref[0, t] = h.astype(h_ref.dtype)
        return h

    state[...] = jax.lax.fori_loop(0, bt, step, state[...])


def ssm_scan_pallas(
    a: jnp.ndarray,   # (B, S, C, N)
    b: jnp.ndarray,   # (B, S, C, N)
    *,
    bt: int = 256,
    bc: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    bsz, s, c, n = a.shape
    assert b.shape == a.shape, (a.shape, b.shape)
    assert s % bt == 0 and c % bc == 0, (s, bt, c, bc)

    spec = pl.BlockSpec((1, bt, bc, n), lambda ib, ic, it: (ib, it, ic, 0))
    return pl.pallas_call(
        functools.partial(_scan_kernel, bt=bt),
        grid=(bsz, c // bc, s // bt),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        scratch_shapes=[pltpu.VMEM((bc, n), jnp.float32)],
        interpret=interpret,
    )(a, b)
