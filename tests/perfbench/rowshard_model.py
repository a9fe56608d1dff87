"""A throwaway model for the tests of a cell on several chips, copied by
them into a copy of the benchmark as ``perfbench/models/rowshard.py``:
least squares by gradient descent in plain JAX, the rows of X sharded
over the devices it is handed. Each device takes the loss and gradient
of its own rows, and a ``psum`` across the devices, the exchange between
chips, adds them up."""

from __future__ import annotations

import contextlib
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from perfbench import check

FAULTS = ("unchanged", "half_batch", "exchange")
_fault = None  # the fault entered, read when a step is built
HIGHEST = jax.lax.Precision.HIGHEST


def rows(cfg: dict) -> int:
    return int(cfg["rows"])


def _mesh(devices) -> Mesh:
    return Mesh(np.array(list(devices)), ("rows",))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, n: int, m: int):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.normal(k1, (n, m), jnp.float32)
    y = (jnp.dot(x, jax.random.normal(k2, (m,), jnp.float32), precision=HIGHEST)
         + jax.random.normal(k3, (n,), jnp.float32))
    return x, y, 0.1 * jax.random.normal(k4, (m,), jnp.float32)


def make_inputs(cfg: dict, feed, seed: int, devices) -> dict:
    if rows(cfg) % len(devices):
        raise ValueError(f"{rows(cfg)} rows do not split over {len(devices)} devices")
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)
    x, y, theta = _make(key, rows(cfg), int(cfg["features"]))
    mesh = _mesh(devices)
    return {"x": jax.device_put(x, NamedSharding(mesh, P("rows"))),
            "y": jax.device_put(y, NamedSharding(mesh, P("rows"))),
            "theta": jax.device_put(theta, NamedSharding(mesh, P()))}


def _step(mesh, n: int, lr: float, fault):
    def body(theta, x, y):
        if fault == "half_batch":
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        count = n // 2 if fault == "half_batch" else n
        r = jnp.dot(x, theta, precision=HIGHEST) - y
        loss, g = 0.5 * jnp.sum(r * r), jnp.dot(r, x, precision=HIGHEST)
        if fault != "exchange":
            loss, g = jax.lax.psum((loss, g), "rows")
        if fault != "unchanged":
            theta = theta - lr * g / count
        return theta, loss / count

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P("rows"), P("rows")),
        out_specs=(P(), P()), check_vma=fault != "exchange"))


class Trainer:
    def __init__(self, cfg: dict, feed, inputs: dict, spans, devices):
        if not feed.full:
            raise ValueError("rowshard trains on every row each step")
        placed = len(inputs["x"].sharding.device_set)
        print(f"rowshard: {len(devices)} devices, x on {placed}", file=sys.stderr)
        self.spans = spans
        self.x, self.y, self.theta = inputs["x"], inputs["y"], inputs["theta"]
        self._step = _step(_mesh(devices), rows(cfg), float(cfg["lr"]), _fault)

    def step(self, i: int):
        with self.spans("step"):
            self.theta, loss = self._step(self.theta, self.x, self.y)
        return loss, self.theta

    @staticmethod
    def loss(out):
        return out[0]

    def state(self) -> dict:
        return {"theta": self.theta}


def readings(cfg: dict, feed, states, losses) -> dict:
    s0, s1, s_last = (np.asarray(s["theta"], np.float64) for s in states)
    return check.readings(losses, {"theta": (s0 - s1) / float(cfg["lr"])},
                          {"theta": s_last - s0})


def work(cfg: dict, feed) -> dict:
    n, m = rows(cfg), int(cfg["features"])
    return {"flops": 4 * n * m, "kernels": {}}


@contextlib.contextmanager
def fault(name: str):
    """The step broken while entered: ``unchanged`` leaves θ as it was,
    ``half_batch`` takes the first half of each device's rows alone,
    ``exchange`` leaves the ``psum`` out (the first device's partial sums
    stand for all)."""
    global _fault
    if name not in FAULTS:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")
    _fault = name
    try:
        yield
    finally:
        _fault = None
