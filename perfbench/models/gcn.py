"""Two-layer GCN (Kipf & Welling) trained with Adam, full graph, through
the program's relational ops: ``gcn_conv`` / ``rel_linear`` under
``db.activate()``, with the Edge relation in the session's catalog, in
one jitted step of the user's own.

The graph comes from a copy of ``repro.data.graphs.synthetic_graph``
(power-law destinations, a self loop per node, symmetric
normalisation), so that a change to the program's generator does not
change the benchmark's traffic.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.optim import adam_init, adam_update
from repro.relational import gcn_conv, rel_linear

from perfbench import check
from perfbench.models import one_chip

#: Adam's first-moment decay, as ``repro.optim.adam_update`` defaults it.
B1 = 0.9


def rows(cfg: dict) -> int:
    return int(cfg["nodes"])


def synthetic_graph(n_nodes: int, n_edges: int, n_feat: int, n_labels: int,
                    seed: int) -> dict:
    """Copy of ``repro.data.graphs.synthetic_graph``, as numpy arrays."""
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n_nodes, size=n_edges)
    dst = (rng.pareto(2.0, size=n_edges) * n_nodes / 8).astype(np.int64) % n_nodes
    loops = np.arange(n_nodes)
    src = np.concatenate([src, loops])
    dst = np.concatenate([dst, loops])
    deg = np.bincount(dst, minlength=n_nodes) + np.bincount(src, minlength=n_nodes)
    w = 1.0 / np.sqrt(deg[src] * deg[dst]).astype(np.float32)
    keys = np.stack([src, dst], axis=1).astype(np.int32)
    x = rng.normal(size=(n_nodes, n_feat)).astype(np.float32)
    y = rng.integers(0, n_labels, size=n_nodes).astype(np.int32)
    return {"keys": keys, "w": w, "x": x, "y": y}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _init_params(key, feat: int, hidden: int, classes: int) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (feat, hidden), jnp.float32) * feat ** -0.5,
        "w2": jax.random.normal(k2, (hidden, classes), jnp.float32) * hidden ** -0.5,
    }


def make_inputs(cfg: dict, feed, seed: int, devices) -> dict:
    one_chip(devices)
    g = synthetic_graph(cfg["nodes"], cfg["edges"], cfg["features"],
                        cfg["classes"], seed)
    out = {k: jax.device_put(v) for k, v in g.items()}
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)
    out["params"] = _init_params(key, cfg["features"], cfg["hidden"],
                                 cfg["classes"])
    return out


def _xent(logits, y):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _loss(p, x, keys, w, y):
    h = jax.nn.relu(rel_linear(gcn_conv(x, keys, w), p["w1"]))
    return _xent(rel_linear(gcn_conv(h, keys, w), p["w2"]), y)


def _train_step(p, opt, x, keys, w, y, *, lr: float):
    loss, grads = jax.value_and_grad(_loss)(p, x, keys, w, y)
    p, opt = adam_update(p, grads, opt, lr=lr)
    return p, opt, loss


class Trainer:
    """The user's training loop: one jitted step of the relational ops,
    traced under the session that holds the Edge relation."""

    def __init__(self, cfg: dict, feed, inputs: dict, spans, devices):
        one_chip(devices)
        if not feed.full:
            raise ValueError("the GCN trains on the full graph only")
        n = rows(cfg)
        self.spans = spans
        self.db = repro.Database()
        self.db.put("Edge", repro.CooRelation(inputs["keys"], inputs["w"], (n, n)),
                    keys=("src", "dst"))
        self.args = (inputs["x"], inputs["keys"], inputs["w"], inputs["y"])
        self.params = inputs["params"]
        self.opt = adam_init(self.params)
        self._step = jax.jit(functools.partial(_train_step, lr=cfg["lr"]))

    def step(self, i: int):
        with self.spans("step"), self.db.activate():
            self.params, self.opt, loss = self._step(self.params, self.opt,
                                                     *self.args)
        return loss, self.params, self.opt

    @staticmethod
    def loss(out):
        return out[0]

    def state(self) -> dict:
        return {"params": self.params, "opt": self.opt}


def readings(cfg: dict, feed, states, losses) -> dict:
    """The first gradient from Adam's first moment after one step
    (m = (1 - b1) g from m = 0); the change from the parameters."""
    s0, s1, s_last = states
    grads = {k: np.asarray(m, np.float64) / (1.0 - B1)
             for k, m in s1["opt"]["mu"].items()}
    changes = {k: np.asarray(s_last["params"][k], np.float64)
               - np.asarray(s0["params"][k], np.float64)
               for k in s0["params"]}
    return check.readings(losses, grads, changes)


def _mm(m: int, k: int, n: int) -> tuple:
    return 2 * m * k * n, 4 * (m * k + k * n + m * n)


def work(cfg: dict, feed) -> dict:
    """What one step requires. A convolution is E·D products (w · h_src)
    and E·D adds (the Σ by destination); the step makes two forward
    convolutions (D = features, hidden) and one backward (D = hidden:
    the loss is not differentiated by the features). The Σ kernel's
    share is its adds, with E·D·4 bytes of messages, E·4 of ids and
    S·D·4 of output. The gather kernel takes the three convolutions'
    inputs by source, one row per edge: E·D·4 bytes read, E·D·4 written
    and E·4 of row ids, and no arithmetic. The dense products: two
    forward, and backward the two weight gradients and the hidden
    layer's input gradient; the program takes the two forward ones
    through the blocked matmul kernel and the backward ones as XLA dots.
    E counts the self loops and no padding."""
    n, f, h, c = (int(cfg[k]) for k in ("nodes", "features", "hidden", "classes"))
    e = int(cfg["edges"]) + n  # a self loop per node
    segsum = [(e * d, 4 * (e * d + e + n * d)) for d in (f, h, h)]
    gathers = [(0, 4 * (2 * e * d + e)) for d in (f, h, h)]
    forward = [_mm(n, f, h), _mm(n, h, c)]
    backward = [_mm(h, n, c), _mm(n, c, h), _mm(f, n, h)]  # dW2, dH, dW1
    flops = 2 * e * (f + h + h) + sum(fl for fl, _ in forward + backward)
    return {"flops": flops,
            "kernels": {"segment_sum": segsum, "gather_join": gathers,
                        "blocked_matmul": forward}}


FAULTS = ("unchanged", "half_batch")


def _unchanged_adam(params, grads, state, **kw):
    return params, state


def _half_xent(xent):
    def half(logits, y):
        return xent(logits[::2], y[::2])
    return half


@contextlib.contextmanager
def fault(name: str):
    """This driver's step broken while entered, for the tests that see
    ``correct`` come out false: ``unchanged`` skips Adam's update, so the
    state stays as it was; ``half_batch`` takes the loss's mean over
    every second node alone. A step built inside traces the broken
    code."""
    global adam_update, _xent
    saved = adam_update, _xent
    if name == "unchanged":
        adam_update = _unchanged_adam
    elif name == "half_batch":
        _xent = _half_xent(_xent)
    else:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")
    try:
        yield
    finally:
        adam_update, _xent = saved
