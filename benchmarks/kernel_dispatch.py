"""Kernel dispatch: the routed segment-sum / blocked-matmul tiers head to
head, raw and under compiled engine steps.

Three sections:

  segsum-raw /     the two dispatch ops at GCN- and logreg-representative
  matmul-raw       shapes, per tier — ``jnp`` (the compiler's default
                   lowering) vs ``ref`` (the kernel packages' jnp oracle)
                   vs ``pallas`` where a TPU is attached
  engine-*-grad    compiled logreg and GCN gradient steps per tier; the
                   jnp-tier result is the correctness oracle, asserted to
                   atol 1e-5
  interpret-probe  Pallas interpreter-mode at small shapes: the CPU
                   stand-in proving the TPU kernels' logic inside a
                   compiled step, also asserted against jnp

On CPU the jnp-vs-ref delta is the headline number (ref is the oracle the
Pallas kernels are tested against, so the delta isolates dispatch-layer
overhead — it should be ≈1.0x); on TPU the pallas rows report the actual
kernel speedup over the jnp tier.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fra
from repro.core.autodiff import ra_autodiff
from repro.core.engine import engine_for
from repro.core.kernels import make_table, resolve_impl, scale_kernel
from repro.core.keys import TRUE, identity_key
from repro.core.relation import CooRelation, DenseRelation
from repro.relational.gcn import gcn_square_loss

from .common import record, timeit
from .logreg import logreg_query

ATOL = 1e-5


def _tiers():
    if jax.default_backend() == "tpu":
        return ("jnp", "ref", "pallas")
    return ("jnp", "ref")


def _logreg_prog(n: int):
    # mean (not sum) loss keeps gradient magnitudes O(1), so the atol-1e-5
    # cross-tier check measures kernel agreement, not summation scale
    q = logreg_query()
    mean = fra.Select(TRUE, identity_key(0), scale_kernel(1.0 / n), q.root)
    return ra_autodiff(fra.Query(mean, inputs=q.inputs))


def _logreg_env(rng, n: int, m: int):
    return {
        "Rx": DenseRelation(jnp.asarray(rng.normal(size=(n, m)), jnp.float32), 2),
        "Ry": DenseRelation(
            jnp.asarray(rng.integers(0, 2, size=n), jnp.float32), 1
        ),
        "theta": DenseRelation(
            jnp.asarray(rng.normal(size=m) * 0.01, jnp.float32), 1
        ),
    }


def _gcn_prog(n: int):
    return ra_autodiff(gcn_square_loss(n, const_edges=True))


def _gcn_env(rng, n: int, e: int, d: int):
    src = rng.integers(0, n, size=e)
    dst = rng.integers(0, n, size=e)
    return {
        "Edge": CooRelation(
            jnp.asarray(np.stack([src, dst], 1), jnp.int32),
            jnp.asarray(rng.normal(size=e) / np.sqrt(e / n), jnp.float32),
            (n, n),
        ),
        "Node": DenseRelation(
            jnp.asarray(rng.normal(size=(n, d)), jnp.float32), 1
        ),
    }


def _grad_leaves(out, grads):
    leaves = [np.asarray(out.data)]
    for name in sorted(grads):
        g = grads[name]
        leaves.append(np.asarray(g.values if isinstance(g, CooRelation) else g.data))
    return leaves


def _bench_raw_segsum() -> None:
    rng = np.random.default_rng(0)
    for e, d, s in ((320_000, 32, 20_000), (22_000, 64, 4_000)):
        msg = jnp.asarray(rng.normal(size=(e, d)), jnp.float32)
        seg = jnp.asarray(rng.integers(0, s, size=e), jnp.int32)
        info = {"nnz": e, "dim": d, "num_segments": s, "dtype": msg.dtype}
        base_us, base_out = None, None
        for tier in _tiers():
            impl = resolve_impl("segment_sum", info, make_table(tier))
            fn = jax.jit(lambda m, sg, _f=impl.fn, _s=s: _f(m, sg, _s))
            us = timeit(fn, msg, seg, iters=5, warmup=2)
            out = np.asarray(fn(msg, seg))
            if tier == "jnp":
                base_us, base_out = us, out
                derived = f"E={e};D={d};S={s}"
            else:
                np.testing.assert_allclose(out, base_out, rtol=1e-4, atol=1e-4)
                derived = f"vs_jnp={base_us / us:.2f}x"
            record(f"kernel_dispatch/segsum-raw/E{e}-D{d}-S{s}/{tier}", us, derived)


def _bench_raw_matmul() -> None:
    rng = np.random.default_rng(1)
    for m, k, n in ((4096, 256, 256), (20_000, 64, 32)):
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        y = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        info = {"m": m, "k": k, "n": n, "dtype": x.dtype}
        base_us, base_out = None, None
        for tier in _tiers():
            impl = resolve_impl("blocked_matmul", info, make_table(tier))
            fn = jax.jit(impl.fn)
            us = timeit(fn, x, y, iters=5, warmup=2)
            out = np.asarray(fn(x, y))
            if tier == "jnp":
                base_us, base_out = us, out
                derived = f"m={m};k={k};n={n}"
            else:
                np.testing.assert_allclose(
                    out, base_out, rtol=1e-4, atol=1e-3 * np.sqrt(k)
                )
                derived = f"vs_jnp={base_us / us:.2f}x"
            record(f"kernel_dispatch/matmul-raw/{m}x{k}x{n}/{tier}", us, derived)


def _bench_engine(tag: str, prog, env, tiers, iters: int = 10) -> None:
    eng = engine_for(prog)
    base_us, base_leaves = None, None
    for tier in tiers:
        comp = eng.lower(env, dispatch=tier).compile()
        out, grads = comp(env)                       # trace once
        leaves = _grad_leaves(out, grads)
        t0 = eng.trace_count
        us = timeit(lambda: comp(env), iters=iters, warmup=2)
        retraces = eng.trace_count - t0
        assert retraces == 0, f"{tag}/{tier} re-lowered on a fixed signature"
        sites = ",".join(
            f"{k}={v}" for k, v in sorted(comp.resolutions.items())
        )
        if tier == "jnp":
            base_us, base_leaves = us, leaves
            derived = sites
        else:
            for got, want in zip(leaves, base_leaves):
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=ATOL)
            derived = f"vs_jnp={base_us / us:.2f}x;{sites}"
        record(f"kernel_dispatch/{tag}/{tier}", us, derived)


def _bench_interpret_probe() -> None:
    """Pallas interpreter mode inside compiled steps, small shapes: the
    CPU correctness probe for the TPU kernel logic (timed for visibility,
    not for speed — interpret mode is slow by construction)."""
    rng = np.random.default_rng(2)
    for tag, prog, env in (
        ("logreg", _logreg_prog(48), _logreg_env(rng, 48, 12)),
        ("gcn", _gcn_prog(16), _gcn_env(rng, 16, 40, 8)),
    ):
        eng = engine_for(prog)
        out_j, grads_j = eng.lower(env, dispatch="jnp").compile()(env)
        comp = eng.lower(env, dispatch="interpret").compile()
        out_i, grads_i = comp(env)
        for got, want in zip(
            _grad_leaves(out_i, grads_i), _grad_leaves(out_j, grads_j)
        ):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=ATOL)
        us = timeit(lambda: comp(env), iters=2, warmup=1)
        record(
            f"kernel_dispatch/interpret-probe/{tag}", us,
            "matches_jnp_atol=1e-5",
        )


def run() -> None:
    tiers = _tiers()
    _bench_raw_segsum()
    _bench_raw_matmul()
    rng = np.random.default_rng(3)
    _bench_engine(
        "engine-logreg-grad", _logreg_prog(8192), _logreg_env(rng, 8192, 256), tiers
    )
    _bench_engine(
        "engine-gcn-grad", _gcn_prog(4000), _gcn_env(rng, 4000, 22_000, 64), tiers
    )
    _bench_interpret_probe()


if __name__ == "__main__":
    from .common import ROWS, emit_header, emit_json

    emit_header()
    run()
    emit_json("BENCH_kernel_dispatch.json", ROWS)
