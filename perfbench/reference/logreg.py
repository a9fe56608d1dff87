"""Plain JAX logistic regression: the summed cross-entropy of
sigmoid(X·θ) against y, gradient descent on θ. Imports nothing of the
program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import check


def _loss(theta, x, y):
    yhat = jax.nn.sigmoid(x @ theta.astype(x.dtype))
    loss = jnp.sum(-y * jnp.log(yhat) + (y - 1.0) * jnp.log1p(-yhat))
    return loss.astype(jnp.float32)


@jax.jit
def _step(theta, x, y, scale, lr):
    loss, g = jax.value_and_grad(_loss)(theta, x, y)
    loss, g = loss * scale, g * scale
    return theta - lr * g, loss, g


def run(cfg: dict, inputs: dict, dtype=jnp.float32, fault=None) -> dict:
    """``check.readings`` of ``check.STEPS`` steps from the benchmark's
    inputs: every row each step (``x``), or step i on batch ``xs[i]``.
    The model computes in ``dtype``; θ stays float32."""
    if fault not in (None, "half_batch"):
        raise ValueError(f"unknown fault {fault!r}")
    theta0 = inputs["theta"]
    theta, losses, first = theta0, [], None
    with jax.default_matmul_precision("highest"):
        for i in range(check.STEPS):
            if "x" in inputs:
                xb, yb = inputs["x"], inputs["y"]
            else:
                xb, yb = inputs["xs"][i], inputs["ys"][i]
            lr = cfg["step_size"] / xb.shape[0]
            scale = 1.0
            if fault == "half_batch":
                half = xb.shape[0] // 2
                xb, yb, scale = xb[:half], yb[:half], 2.0
            theta, loss, g = _step(theta, jnp.asarray(xb, dtype),
                                   jnp.asarray(yb, dtype), scale, lr)
            losses.append(loss)
            first = g if first is None else first
    change = np.asarray(theta, np.float64) - np.asarray(theta0, np.float64)
    return check.readings(losses, {"theta": first}, {"theta": change})
