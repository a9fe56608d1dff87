"""Logistic regression (paper §2.3) trained by gradient descent through
the program's SQL front door: ``db.sql(LOGREG_SQL, wrt=("theta",))`` →
``QueryHandle.step``, with ``theta`` put back into the catalog after
every step as a user's loop does. With a mini-batch mix each step puts
its rows as ``Rx`` / ``Ry`` with ``db.put``'s default arguments: the
table stays on the device, cut into the feed's batches in set-up."""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.core.relation import DenseRelation
from repro.core.session import QueryHandle

from perfbench import check
from perfbench.models import one_chip

LOGREG_SQL = """
mm   := SELECT Rx.row, SUM(multiply(Rx.val, theta.val))
        FROM Rx, theta WHERE Rx.col = theta.col GROUP BY Rx.row;
pred := SELECT mm.row, logistic(mm.val) FROM mm;
SELECT SUM(xent(pred.val, Ry.val)) FROM pred, Ry WHERE pred.row = Ry.row
"""


def rows(cfg: dict) -> int:
    return int(cfg["rows"])


def lr(cfg: dict, feed) -> float:
    """The step size over the rows of a step: the loss sums them."""
    return float(cfg["step_size"]) / feed.batch_rows


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, n: int, m: int):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    x = jax.random.normal(k1, (n, m), jnp.float32)
    plane = jax.random.normal(k2, (m,), jnp.float32) * m ** -0.5
    # label noise as large as the signal: the optimum stays finite and
    # no logit comes near where sigmoid rounds to 0 or 1 in float32
    z = jnp.dot(x, plane, precision=jax.lax.Precision.HIGHEST)
    y = z + jax.random.normal(k4, (n,), jnp.float32) > 0
    theta = 0.01 * jax.random.normal(k3, (m,), jnp.float32)
    return x, y.astype(jnp.float32), theta


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_batches(key, n: int, m: int, ids):
    """The table cut into the feed's batches, in one call: a row gather
    per step from a (400,000 × 2,000) table would transpose all of it,
    since the TPU lays such an array out column-major."""
    x, y, theta = _make(key, n, m)
    xs, ys = x[ids], y[ids]
    return ([xs[i] for i in range(ids.shape[0])],
            [ys[i] for i in range(ids.shape[0])], theta)


def make_inputs(cfg: dict, feed, seed: int, devices) -> dict:
    """Full batch: ``x``, ``y``, ``theta``. Mini-batch: the table on the
    device as the feed's batches, ``xs[i]`` / ``ys[i]``, and ``theta``."""
    one_chip(devices)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)
    n, m = rows(cfg), int(cfg["features"])
    if feed.full:
        x, y, theta = _make(key, n, m)
        return {"x": x, "y": y, "theta": theta}
    xs, ys, theta = _make_batches(key, n, m, jnp.asarray(feed.batches))
    return {"xs": xs, "ys": ys, "theta": theta}


class Trainer:
    """The user's loop: put the step's rows (mini-batch), step the
    handle, update theta and put it back."""

    def __init__(self, cfg: dict, feed, inputs: dict, spans, devices):
        one_chip(devices)
        self.spans = spans
        self.lr = lr(cfg, feed)
        self.theta = inputs["theta"]
        self.db = repro.Database()
        self.batches = None
        if feed.full:
            self.db.put("Rx", inputs["x"], keys=("row", "col"))
            self.db.put("Ry", inputs["y"], keys=("row",))
        else:
            self.batches = list(zip(inputs["xs"], inputs["ys"]))
            self._put_rows(0)
        self.db.put("theta", self.theta, keys=("col",))
        self.handle = self.db.sql(LOGREG_SQL, wrt=("theta",))

    def _put_rows(self, i: int) -> None:
        xb, yb = self.batches[i % len(self.batches)]
        with self.spans("put"):
            self.db.put("Rx", xb, keys=("row", "col"))
            self.db.put("Ry", yb, keys=("row",))

    def step(self, i: int):
        if self.batches is not None:
            self._put_rows(i)
        with self.spans("step"):
            loss, grads = self.handle.step()
        with self.spans("update"):
            self.theta = self.theta - self.lr * grads["theta"].data
        with self.spans("put"):
            self.db.put("theta", self.theta, keys=("col",))
        return loss.data, self.theta

    @staticmethod
    def loss(out):
        return out[0]

    def state(self) -> dict:
        return {"theta": self.theta}


def readings(cfg: dict, feed, states, losses) -> dict:
    """The first gradient from the first update, θ1 = θ0 - lr·g."""
    s0, s1, s_last = (np.asarray(s["theta"], np.float64) for s in states)
    return check.readings(losses, {"theta": (s0 - s1) / lr(cfg, feed)},
                          {"theta": s_last - s0})


def work(cfg: dict, feed) -> dict:
    """What one step requires: X·θ forward and Xᵀ·g backward over the
    step's rows, 2·r·m operations each, reading X once each
    ((r·m + m + r)·4 bytes)."""
    r, m = feed.batch_rows, int(cfg["features"])
    mm = (2 * r * m, 4 * (r * m + m + r))
    return {"flops": 2 * mm[0], "kernels": {"blocked_matmul": [mm, mm]}}


FAULTS = ("unchanged", "half_batch")


def _unchanged(step):
    def unchanged(self, **kw):
        out, grads = step(self, **kw)
        return out, {k: DenseRelation(jnp.zeros_like(g.data), g.key_arity)
                     for k, g in grads.items()}
    return unchanged


def _half_batch(step):
    def half(self, **kw):
        db = self.db
        full = {n: db.get(n) for n in ("Rx", "Ry")}
        rows = full["Rx"].data.shape[0] // 2
        for n, rel in full.items():
            db.put(n, DenseRelation(rel.data[:rows], rel.key_arity))
        try:
            out, grads = step(self, **kw)
        finally:
            for n, rel in full.items():
                db.put(n, rel)
        two = lambda r: DenseRelation(2 * r.data, r.key_arity)  # noqa: E731
        return two(out), {k: two(g) for k, g in grads.items()}
    return half


@contextlib.contextmanager
def fault(name: str):
    """The program's ``QueryHandle.step`` broken while entered, for the
    tests that see ``correct`` come out false: ``unchanged`` returns a
    zero gradient, so θ stays as it was; ``half_batch`` steps on the
    first half of the step's rows and doubles the summed loss and
    gradient, the mean taken over the rest."""
    wrap = {"unchanged": _unchanged, "half_batch": _half_batch}.get(name)
    if wrap is None:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")
    step = QueryHandle.step
    QueryHandle.step = wrap(step)
    try:
        yield
    finally:
        QueryHandle.step = step
