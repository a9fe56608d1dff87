"""Plain JAX two-layer GCN with Adam on several chips: the one-chip
reference's model (``reference/gcn.py``: gather by source, scatter-add
by destination, dense products at ``highest``, mean cross-entropy over
the nodes) and its Adam, computed where the graph is too large for one
chip. Imports nothing of the program.

It takes the generator's edge list as the generator made it
(``graph_keys``, ``graph_w``), not the program's owner-partitioned,
padded layout of it, and the chips from the mesh of the replicated
features.

Departures from the one-chip reference, none of which changes what is
computed:

* each chip takes an equal run of the generator's edges, in their
  order (the last padded with ids -1 and weight 0), and scatter-adds
  their messages into a whole-graph partial sum, which a
  ``psum_scatter`` adds up and leaves with the chip owning a quarter of
  the rows; the dense layers, the loss and the gathers' tables
  (``all_gather`` of the owned rows) follow those rows, in one
  ``shard_map`` over the chips;
* a convolution runs in blocks of ``BLOCK`` edges (a ``fori_loop`` of
  ``.at[dst].add(w · h[src])``), and its gradient is the reversed-edge
  convolution of the cotangent in the same blocks (a ``custom_vjp``), so
  no edges × width array exists;
* the node rows are padded with rows that no edge reaches and that the
  loss leaves out, to whole blocks of ``ROWS`` rows a chip (a TPU
  compile of an all-gather of rows that are not takes minutes);
* a node's log-likelihood is read through a one-hot of its label, not
  gathered at it, so that its gradient is a product and not a scatter
  of a row per node.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from perfbench import check
from perfbench.reference.gcn import B1, B2, EPS

#: edges a block of a convolution takes (64 MiB of messages at width 256;
#: a TPU compile of a scatter takes longer the more rows it adds)
BLOCK = 1 << 16

#: a chip's node rows are a multiple of this
ROWS = 128


def _spread(h, src, dst, w, rows: int, axis: str):
    """Σ_e w_e · h[src_e] into row dst_e of a (rows, D) sum, in blocks of
    the edges (padding edges carry ids -1 and weight 0): one chip's
    partial sum, of its own edges, varying over ``axis``."""
    e = src.shape[0]
    block = min(BLOCK, e)
    nb = -(-e // block)
    pad = nb * block - e
    src, dst = (jnp.pad(a, (0, pad), constant_values=-1) for a in (src, dst))
    w = jnp.pad(w, (0, pad))

    def body(i, acc):
        s, d, ww = (jax.lax.dynamic_slice_in_dim(a, i * block, block)
                    for a in (src, dst, w))
        msg = jnp.where((s >= 0)[:, None], ww[:, None] * h[jnp.maximum(s, 0)], 0)
        return acc.at[d].add(msg.astype(acc.dtype), mode="drop")

    zero = jax.lax.pcast(jnp.zeros((rows, h.shape[1]), h.dtype), (axis,), to="varying")
    return jax.lax.fori_loop(0, nb, body, zero)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _conv(h, src, dst, w, rows, axis):
    return _spread(h, src, dst, w, rows, axis)


def _conv_fwd(h, src, dst, w, rows, axis):
    return _spread(h, src, dst, w, rows, axis), (h.shape[0], src, dst, w)


def _conv_bwd(rows, axis, res, g):
    n, src, dst, w = res
    return _spread(g, dst, src, w, n, axis), None, None, None


_conv.defvjp(_conv_fwd, _conv_bwd)


def _model(axis: str):
    """The per-chip body: the mean cross-entropy over the nodes the mask
    keeps, each chip holding its edges and its share of the rows."""

    def conv(h_own, src, dst, w):
        table = jax.lax.all_gather(h_own, axis, tiled=True)
        part = _conv(table, src, dst, w, table.shape[0], axis)
        return jax.lax.psum_scatter(part, axis, scatter_dimension=0, tiled=True)

    def loss(p, x_own, src, dst, w, y_own, mask_own):
        p = jax.tree.map(lambda a: a.astype(x_own.dtype), p)
        h = jax.nn.relu(conv(x_own, src, dst, w) @ p["w1"])
        logp = jax.nn.log_softmax(conv(h, src, dst, w) @ p["w2"])
        nll = -jnp.sum(logp * jax.nn.one_hot(y_own, logp.shape[1], dtype=logp.dtype), axis=1)
        total = jax.lax.psum(jnp.sum(nll * mask_own).astype(jnp.float32), axis)
        return total / jax.lax.psum(jnp.sum(mask_own).astype(jnp.float32), axis)

    return loss


def run(cfg: dict, inputs: dict, dtype=jnp.float32, fault=None) -> dict:
    """``check.readings`` of ``check.STEPS`` Adam steps on the full graph,
    on the chips of the first axis of the mesh that the features are
    placed on. The model computes in ``dtype``; the parameters and
    Adam's state stay float32."""
    mesh = inputs["x"].sharding.mesh
    axis = mesh.axis_names[0]
    chips = mesh.shape[axis]
    x, y = np.asarray(inputs["x"]), np.asarray(inputs["y"])
    n = x.shape[0]
    rows = -(-n // (chips * ROWS)) * chips * ROWS
    mask = np.zeros(rows, np.float32)
    mask[:n] = 1.0
    if fault == "half_batch":
        mask[1::2] = 0.0
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    own = NamedSharding(mesh, P(axis))
    x = jax.device_put(np.pad(x, ((0, rows - n), (0, 0))).astype(dtype),
                       NamedSharding(mesh, P(axis, None)))
    y = jax.device_put(np.pad(y, (0, rows - n)), own)
    mask = jax.device_put(mask.astype(dtype), own)
    keys, w = np.asarray(inputs["graph_keys"]), np.asarray(inputs["graph_w"])
    pad = -keys.shape[0] % chips
    src, dst = (jax.device_put(np.pad(keys[:, i], (0, pad), constant_values=-1), own)
                for i in (0, 1))
    w = jax.device_put(np.pad(w, (0, pad)), own).astype(dtype)
    loss = jax.shard_map(
        _model(axis), mesh=mesh,
        in_specs=(P(), P(axis, None), P(axis), P(axis), P(axis), P(axis), P(axis)),
        out_specs=P())

    @jax.jit
    def step(p, m, v, t, data):
        value, g = jax.value_and_grad(loss)(p, *data)
        m = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, m, g)
        v = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, v, g)
        p = jax.tree.map(
            lambda p, m, v: p - cfg["lr"] * (m / (1 - B1 ** t))
            / (jnp.sqrt(v / (1 - B2 ** t)) + EPS),
            p, m, v)
        return p, m, v, value, g

    p0 = inputs["params"]
    p = p0
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for t in range(1, check.STEPS + 1):
            p, m, v, value, g = step(p, m, v, t, (x, src, dst, w, y, mask))
            losses.append(value)
            first = g if first is None else first
    change = {k: np.asarray(p[k], np.float64) - np.asarray(p0[k], np.float64)
              for k in p}
    return check.readings(losses, first, change)
