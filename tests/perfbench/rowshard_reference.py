"""The plain reference of ``rowshard_model.py``, copied by the tests into
a copy of the benchmark as ``perfbench/reference/rowshard.py``: least
squares by gradient descent over every row on one device."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import check


def run(cfg: dict, inputs: dict, dtype=jnp.float32, fault=None) -> dict:
    if fault not in (None, "half_batch"):
        raise ValueError(f"unknown fault {fault!r}")
    x = jnp.asarray(np.asarray(inputs["x"]), dtype)
    y = jnp.asarray(np.asarray(inputs["y"]), dtype)
    if fault == "half_batch":
        x, y = x[::2], y[::2]
    lr = float(cfg["lr"])
    theta0 = jnp.asarray(np.asarray(inputs["theta"]))
    theta, losses, first = theta0, [], None
    with jax.default_matmul_precision("highest"):
        for _ in range(check.STEPS):
            r = x @ theta.astype(dtype) - y
            losses.append((0.5 * jnp.sum(r * r) / x.shape[0]).astype(jnp.float32))
            g = (r @ x / x.shape[0]).astype(jnp.float32)
            first = g if first is None else first
            theta = theta - lr * g
    change = np.asarray(theta, np.float64) - np.asarray(theta0, np.float64)
    return check.readings(losses, {"theta": first}, {"theta": change})
