"""Plain JAX two-layer GCN with Adam: gather by source, scatter-add by
destination, dense products, mean cross-entropy over the nodes.
Imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import check

B1, B2, EPS = 0.9, 0.999, 1e-8


def _loss(p, x, src, dst, w, y, mask):
    p = jax.tree.map(lambda a: a.astype(x.dtype), p)

    def conv(h):
        return jnp.zeros_like(h).at[dst].add(w[:, None] * h[src])

    h = jax.nn.relu(conv(x) @ p["w1"])
    logp = jax.nn.log_softmax(conv(h) @ p["w2"])
    nll = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
    return (jnp.sum(nll * mask) / jnp.sum(mask)).astype(jnp.float32)


def _step(p, m, v, t, x, src, dst, w, y, mask, lr):
    loss, g = jax.value_and_grad(_loss)(p, x, src, dst, w, y, mask)
    m = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, m, g)
    v = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, v, g)
    p = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - B1 ** t))
        / (jnp.sqrt(v / (1 - B2 ** t)) + EPS),
        p, m, v)
    return p, m, v, loss, g


def run(cfg: dict, inputs: dict, dtype=jnp.float32, fault=None) -> dict:
    """``check.readings`` of ``check.STEPS`` Adam steps on the full graph.
    The model computes in ``dtype``; the parameters and Adam's state
    stay float32."""
    x, w = jnp.asarray(inputs["x"], dtype), jnp.asarray(inputs["w"], dtype)
    src, dst = inputs["keys"][:, 0], inputs["keys"][:, 1]
    y = inputs["y"]
    mask = jnp.ones(y.shape, dtype)
    if fault == "half_batch":
        mask = (jnp.arange(y.shape[0]) % 2 == 0).astype(dtype)
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    p0 = inputs["params"]
    p = p0
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    step = jax.jit(_step)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for t in range(1, check.STEPS + 1):
            p, m, v, loss, g = step(p, m, v, t, x, src, dst, w, y, mask,
                                    cfg["lr"])
            losses.append(loss)
            first = g if first is None else first
    change = {k: np.asarray(p[k], np.float64) - np.asarray(p0[k], np.float64)
              for k in p}
    return check.readings(losses, first, change)
