"""Two-layer GCN (Kipf & Welling) trained with Adam on the full graph,
on several chips: the edge relation's nnz rows sharded over a data mesh
of the chips the cell is handed, through the program's relational ops
(``gcn_conv`` / ``rel_linear``) under ``repro.Database(mesh=...)``, in
the one-chip GCN model's jitted step of the user's own.

The graph generator, the initial parameters, the step, the loss and the
readings are the one-chip GCN model's (``perfbench.models.gcn``). The
edges are owner-partitioned by destination
(``relational.gcn.partitioned_edges``) and put on the chips nnz-sharded;
the features, labels and parameters are replicated. The program's
join-aggregates then run per chip and exchange their partial sums
(``repro.core.compiler.exchange``), which the ``exchange`` fault breaks.
The generator's own edge list stays in the inputs, on the host, for the
reference, which shares nothing else with the program's layout.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

import repro
from repro.core import compiler
from repro.kernels.gather.gather import ROWS_PER_CALL
from repro.kernels.segsum import segsum
from repro.optim import adam_init
from repro.relational import partitioned_edges

from perfbench.models import gcn

rows = gcn.rows
readings = gcn.readings


def mesh_of(devices) -> Mesh:
    """The data mesh of ``devices``: axes ``("data", "model")`` of sizes
    ``(len(devices), 1)``, both ``Auto``, as ``make_host_mesh(model=1)``
    builds them over a host's devices."""
    return Mesh(np.array(list(devices)).reshape(len(devices), 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)


def make_inputs(cfg: dict, feed, seed: int, devices) -> dict:
    n = rows(cfg)
    g = gcn.synthetic_graph(n, cfg["edges"], cfg["features"], cfg["classes"], seed)
    edge = partitioned_edges(g["keys"], g["w"], n, len(devices))
    mesh = mesh_of(devices)
    whole = NamedSharding(mesh, P())
    out = {
        "keys": jax.device_put(edge.keys, NamedSharding(mesh, P("data", None))),
        "w": jax.device_put(edge.values, NamedSharding(mesh, P("data"))),
        "x": jax.device_put(g["x"], whole),
        "y": jax.device_put(g["y"], whole),
        # the generator's edges in its own order, for the reference
        "graph_keys": g["keys"],
        "graph_w": g["w"],
    }
    del edge, g
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)
    params = gcn._init_params(key, cfg["features"], cfg["hidden"], cfg["classes"])
    out["params"] = jax.device_put(params, whole)
    return out


class Trainer:
    """The user's training loop: the one-chip model's jitted step,
    traced under a session whose mesh is the cell's chips and whose
    catalog holds the Edge relation."""

    def __init__(self, cfg: dict, feed, inputs: dict, spans, devices):
        if not feed.full:
            raise ValueError("the GCN trains on the full graph only")
        n = rows(cfg)
        self.spans = spans
        self.db = repro.Database(mesh=mesh_of(devices))
        self.db.put("Edge", repro.CooRelation(inputs["keys"], inputs["w"], (n, n)),
                    keys=("src", "dst"))
        self.args = (inputs["x"], inputs["keys"], inputs["w"], inputs["y"])
        self.params = inputs["params"]
        self.opt = adam_init(self.params)
        self._step = jax.jit(functools.partial(gcn._train_step, lr=cfg["lr"]))

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


def _launches(ec: int, d: int, segments: int) -> tuple:
    """``(gather launches, Σ launches)`` of one join-aggregate over ``ec``
    edges a chip at width ``d`` into ``segments`` rows: the program's
    blocks (``compiler.block_rows``), the gather's launches of
    ``ROWS_PER_CALL`` rows and the Σ's visit schedule (one visit per
    512-edge block and per 128-row tile, ``segsum.launches``) in each."""
    rows, blocks = compiler.block_rows(ec, 4 * d)
    gathers = -(-rows // min(rows, ROWS_PER_CALL))
    sums = segsum.launches(-(-rows // 512) + -(-segments // 128))[0]
    return blocks * gathers, blocks * sums


def work(cfg: dict, feed) -> dict:
    """What one step requires: ``flops`` over all chips as the one-chip
    model counts them (``gcn.work``). The kernels are each chip's, over
    its E/chips edges (E counts the self loops; E_c is E padded to a
    multiple of the configuration's ``chips``, over them), one call per
    convolution (D = features, hidden, hidden):

    * ``join_agg``, the fused gather ⊗ Σ: 2·E_c·D operations (the
      products and the adds) and the least bytes the call moves: E_c·12
      of ids and weights, E_c·D·4 of gathered rows and S·D·4 of output;
    * ``gather_join`` and ``segment_sum``, the kernels inside it, as the
      one-chip model counts them at E_c edges. A call runs as several
      launches of each (``_launches``), and the trace has an event per
      launch, so each call's work is listed as that many equal parts."""
    n, f, h = (int(cfg[k]) for k in ("nodes", "features", "hidden"))
    chips = int(cfg["chips"])
    e = int(cfg["edges"]) + n
    ec = -(-e // chips)
    segments = -(-n // chips) * chips
    join_agg, gathers, sums = [], [], []
    for d in (f, h, h):
        join_agg.append((2 * ec * d, 12 * ec + 4 * ec * d + 4 * n * d))
        k_g, k_s = _launches(ec, d, segments)
        gathers += [(0, 4 * (2 * ec * d + ec) / k_g)] * k_g
        sums += [(ec * d / k_s, 4 * (ec * d + ec + n * d) / k_s)] * k_s
    return {"flops": gcn.work(cfg, feed)["flops"],
            "kernels": {"join_agg": join_agg, "gather_join": gathers,
                        "segment_sum": sums}}


FAULTS = ("unchanged", "half_batch", "exchange")


def _own_partial(partial, axes, *, scatter):
    """Each chip's own rows of its partial sums, added to nothing."""
    if not scatter:
        return partial
    n = partial.shape[0] // jax.lax.psum(1, axes)
    return jax.lax.dynamic_slice_in_dim(partial, jax.lax.axis_index(axes) * n, n)


@contextlib.contextmanager
def fault(name: str):
    """This model's step broken while entered: ``unchanged`` and
    ``half_batch`` as the one-chip model breaks it (``gcn.fault``);
    ``exchange`` leaves the exchange of partial sums between chips out,
    each chip keeping its own partial sums for the rows it owns. A step
    built inside traces the broken code."""
    if name == "exchange":
        saved = compiler.exchange
        compiler.exchange = _own_partial
        try:
            yield
        finally:
            compiler.exchange = saved
    elif name in FAULTS:
        with gcn.fault(name):
            yield
    else:
        raise ValueError(f"unknown fault {name!r}; have {FAULTS}")
