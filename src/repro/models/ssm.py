"""State-space blocks: Mamba-1 (falcon-mamba) and Mamba-2 (zamba2).

The selective scan is a linear recurrence h_t = a_t ⊙ h_{t-1} + b_t, which
we run with ``jax.lax.associative_scan`` — the TPU-native parallel-prefix
form (log-depth, bandwidth-bound) instead of the CUDA kernel the papers
ship. Decode keeps (conv window, ssm state) as carried state and advances
one step in O(1).

Arch-applicability (DESIGN.md): the recurrence is *not* a relational
join-aggregate, so the paper's auto-diff does not cover it — these blocks
use JAX AD for the scan itself, while their projections (in/out/gate/dt)
still go through the relational engine.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.relational import rel_linear

from .common import dense_init


def _assoc_scan(a, b):
    """h_t = a_t * h_{t-1} + b_t along axis 1 (time). a, b: (B, S, ...).
    Returns (cumulative a-product, h)."""

    def combine(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    return jax.lax.associative_scan(combine, (a, b), axis=1)


def selective_scan(a, b, chunk: int = 0):
    """h_t = a_t ⊙ h_{t-1} + b_t along axis 1.

    ``chunk == 0`` runs one parallel prefix over the whole sequence:
    O(S·log₂S) HBM traffic in the (B,S,·) state tensors. ``chunk > 0``
    runs a *sequential* ``lax.scan`` over S/chunk chunks carrying the
    boundary state, with the parallel prefix only within each chunk:
    O(S·(log₂chunk + 2)) traffic — the Mamba-2/SSD blocking adapted to
    XLA (§Perf iteration 1). The carry enters each chunk through the
    cumulative a-product the within-chunk prefix already computes, so the
    extra cost per chunk is one multiply-add."""
    s = a.shape[1]
    if not chunk or s <= chunk or s % chunk:
        return _assoc_scan(a, b)[1]
    nc = s // chunk
    a_c = jnp.moveaxis(
        a.reshape((a.shape[0], nc, chunk) + a.shape[2:]), 1, 0
    )
    b_c = jnp.moveaxis(
        b.reshape((b.shape[0], nc, chunk) + b.shape[2:]), 1, 0
    )
    h0 = jnp.zeros(b.shape[:1] + b.shape[2:], dtype=b.dtype)

    def step(h, ab):
        ac, bc = ab
        pa, hl = _assoc_scan(ac, bc)
        hc = hl + pa * h[:, None]
        return hc[:, -1], hc

    # fully unrolled: few chunks (S/chunk ≤ ~64), no loop overhead, and
    # cost_analysis counts every chunk (honest roofline accounting)
    _, hs = jax.lax.scan(step, h0, (a_c, b_c), unroll=True)
    hs = jnp.moveaxis(hs, 0, 1)
    return hs.reshape((b.shape[0], s) + b.shape[2:])


# ---------------------------------------------------------------------------
# Mamba-1 (falcon-mamba, arXiv:2410.05355)
# ---------------------------------------------------------------------------


def mamba1_init(key, d_model: int, state: int = 16, expand: int = 2,
                conv_width: int = 4, dt_rank: Optional[int] = None,
                dtype=jnp.float32):
    d_inner = expand * d_model
    dt_rank = dt_rank or max(1, d_model // 16)
    ks = jax.random.split(key, 7)
    return {
        "in_proj": dense_init(ks[0], (d_model, 2 * d_inner), dtype=dtype),
        "conv_w": dense_init(ks[1], (conv_width, d_inner), dtype=dtype),
        "conv_b": jnp.zeros((d_inner,), dtype=dtype),
        "x_proj": dense_init(ks[2], (d_inner, dt_rank + 2 * state), dtype=dtype),
        "dt_proj": dense_init(ks[3], (dt_rank, d_inner), dtype=dtype),
        "dt_bias": jnp.zeros((d_inner,), dtype=dtype),
        "a_log": jnp.log(
            jnp.broadcast_to(jnp.arange(1, state + 1, dtype=jnp.float32), (d_inner, state))
        ),
        "d_skip": jnp.ones((d_inner,), dtype=jnp.float32),
        "out_proj": dense_init(ks[4], (d_inner, d_model), dtype=dtype),
    }


def _causal_conv(x, w, b, state: Optional[jnp.ndarray] = None):
    """x: (B,S,C), w: (W,C) depthwise. With ``state`` (B,W-1,C) prepends the
    carried window (decode); returns (y, new_state)."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), dtype=x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(width)
    )
    new_state = xp[:, xp.shape[1] - (width - 1):, :]
    return y + b[None, None, :], new_state


def mamba1_apply(
    p,
    x: jnp.ndarray,                      # (B, S, D)
    *,
    state: Optional[dict] = None,        # decode: {"conv": (B,W-1,C), "ssm": (B,C,N)}
    chunk: int = 0,                      # sequential chunking of the scan
    scan_dtype=jnp.float32,              # state dtype inside the scan
    use_pallas: bool = False,            # single-pass Pallas scan kernel
) -> Tuple[jnp.ndarray, Optional[dict]]:
    b, s, _ = x.shape
    d_inner = p["conv_w"].shape[1]
    n = p["a_log"].shape[1]

    xz = rel_linear(x, p["in_proj"])
    xin, z = jnp.split(xz, 2, axis=-1)

    conv_state = state["conv"] if state is not None else None
    xc, new_conv = _causal_conv(xin, p["conv_w"], p["conv_b"], conv_state)
    xc = jax.nn.silu(xc)

    dbl = rel_linear(xc, p["x_proj"])
    dt_rank = p["dt_proj"].shape[0]
    dt, bmat, cmat = jnp.split(dbl, [dt_rank, dt_rank + n], axis=-1)
    dt = jax.nn.softplus(rel_linear(dt, p["dt_proj"]) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])                            # (C, N)

    dt32 = dt.astype(jnp.float32)                        # (B,S,C)
    da = jnp.exp(dt32[..., None] * a[None, None])        # (B,S,C,N)
    db = dt32[..., None] * bmat.astype(jnp.float32)[:, :, None, :]  # (B,S,C,N)
    bx = db * xc.astype(jnp.float32)[..., None]

    if state is None:
        # da/bx are exp/products computed in f32; the scan itself may run
        # in a narrower state dtype (§Perf iteration 2).
        if use_pallas:
            from repro.kernels.ssm_scan import ssm_scan

            h = ssm_scan(
                da.astype(scan_dtype), bx.astype(scan_dtype),
                256, 8, False, True,
            )
        else:
            h = selective_scan(
                da.astype(scan_dtype), bx.astype(scan_dtype), chunk
            )                                            # (B,S,C,N)
        new_ssm = h[:, -1].astype(jnp.float32)
    else:
        h = da[:, 0] * state["ssm"] + bx[:, 0]           # (B,C,N)
        new_ssm = h
        h = h[:, None]

    y = jnp.einsum("bscn,bsn->bsc", h, cmat.astype(h.dtype))
    y = y.astype(jnp.float32)
    y = y + p["d_skip"][None, None] * xc.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = rel_linear(y, p["out_proj"])
    return out, {"conv": new_conv, "ssm": new_ssm}


# ---------------------------------------------------------------------------
# Mamba-2 / SSD (zamba2, arXiv:2411.15242)
# ---------------------------------------------------------------------------


def mamba2_init(key, d_model: int, state: int = 64, expand: int = 2,
                n_heads: Optional[int] = None, head_dim: int = 64,
                conv_width: int = 4, dtype=jnp.float32):
    d_inner = expand * d_model
    n_heads = n_heads or d_inner // head_dim
    ks = jax.random.split(key, 6)
    # in_proj emits [z, x, B, C, dt]
    d_xbc = d_inner + 2 * state
    return {
        "in_proj": dense_init(
            ks[0], (d_model, 2 * d_inner + 2 * state + n_heads), dtype=dtype
        ),
        "conv_w": dense_init(ks[1], (conv_width, d_xbc), dtype=dtype),
        "conv_b": jnp.zeros((d_xbc,), dtype=dtype),
        "a_log": jnp.zeros((n_heads,), dtype=jnp.float32),
        "dt_bias": jnp.zeros((n_heads,), dtype=jnp.float32),
        "d_skip": jnp.ones((n_heads,), dtype=jnp.float32),
        "norm_scale": jnp.zeros((d_inner,), dtype=dtype),
        "out_proj": dense_init(ks[2], (d_inner, d_model), dtype=dtype),
    }


def mamba2_apply(
    p,
    x: jnp.ndarray,                      # (B, S, D)
    *,
    head_dim: int = 64,
    state_dim: int = 64,
    state: Optional[dict] = None,
    chunk: int = 0,
    scan_dtype=jnp.float32,
    use_pallas: bool = False,
) -> Tuple[jnp.ndarray, dict]:
    from .common import rms_norm

    b, s, _ = x.shape
    nh = p["a_log"].shape[0]
    d_inner = nh * head_dim
    n = state_dim

    zxbcdt = rel_linear(x, p["in_proj"])
    z, xbc, dt = jnp.split(
        zxbcdt, [d_inner, d_inner + d_inner + 2 * n], axis=-1
    )
    conv_state = state["conv"] if state is not None else None
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xbc = jax.nn.silu(xbc)
    xin, bmat, cmat = jnp.split(xbc, [d_inner, d_inner + n], axis=-1)

    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    a = -jnp.exp(p["a_log"])                                      # (H,)
    da = jnp.exp(dt * a[None, None])                              # (B,S,H)

    xh = xin.reshape(b, s, nh, head_dim).astype(jnp.float32)
    bx = (
        dt[..., None, None]
        * bmat.astype(jnp.float32)[:, :, None, :, None]
        * xh[..., None, :]
    )  # (B,S,H,N,P)

    if state is None:
        if use_pallas:
            from repro.kernels.ssm_scan import ssm_scan

            hb, hs, hh = bx.shape[:3]
            da_full = jnp.broadcast_to(da[..., None, None], bx.shape)
            h = ssm_scan(
                da_full.reshape(hb, hs, hh, n * head_dim).astype(scan_dtype),
                bx.reshape(hb, hs, hh, n * head_dim).astype(scan_dtype),
                256, 8, False, True,
            ).reshape(bx.shape)
        else:
            h = selective_scan(
                da[..., None, None].astype(scan_dtype),
                bx.astype(scan_dtype),
                chunk,
            )                                                     # (B,S,H,N,P)
        new_ssm = h[:, -1].astype(jnp.float32)
    else:
        h = da[:, 0, :, None, None] * state["ssm"] + bx[:, 0]
        new_ssm = h
        h = h[:, None]

    y = jnp.einsum("bshnp,bsn->bshp", h, cmat.astype(h.dtype))
    y = y.astype(jnp.float32)
    y = y + p["d_skip"][None, None, :, None] * xh
    y = y.reshape(b, s, d_inner).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm_scale"])
    out = rel_linear(y, p["out_proj"])
    return out, {"conv": new_conv, "ssm": new_ssm}
