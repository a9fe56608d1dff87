"""Smoke run of the relational training path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip path only

One chip, through the entry points a user calls:

* logistic regression (paper §2.3): ``repro.Database`` → ``db.put`` →
  ``db.sql(LOGREG_SQL, wrt=("theta",))`` → ``handle.step()``, on a dense
  524,288 × 1,024 f32 ``Rx`` (2 GiB);
* a two-layer GCN at ogbn-arxiv shape (OGB, arXiv:2005.00687: 169,343
  nodes, 1,166,243 edges plus self loops, 128 features, 40 classes,
  hidden 256) through ``gcn_conv`` / ``rel_linear`` under
  ``db.activate()``, the Edge relation in the catalog.

Each takes one warm-up step and three timed steps, and is compared with a
plain f32 ``jax.value_and_grad`` reference. Every ``segment_sum``,
``gather_join`` and ``blocked_matmul`` site must have resolved to the
``pallas`` tier.

The system runs at the default matmul precision, as a user's program
does. There a TPU takes the f32 operands of a dense product in one bf16
pass, in the Pallas matmul as in XLA's dots; sums (the Σ kernel,
scatter-adds, reductions) stay f32. Logistic regression is compared with
a reference under ``jax.default_matmul_precision("highest")``, within a
bound that admits one bf16 pass (``ONE_PASS_RTOL``). The GCN reference
takes its two dense products at the same default precision as the
system and is held to ``RTOL``: one bf16 pass in those products alone
moves ∂w1 some 6e-2 from f32 (emulated on the CPU at 1/8 of the graph),
so only a like-for-like reference shows whether the Σ kernel sums in f32.

``--chips 4`` runs only what exists across chips, each step against the
same step on one device in the same process: the GCN gradient step with
the edge nnz rows sharded four ways (``partitioned_edges`` on
``make_host_mesh(model=1)``), and the logistic-regression step on
``Database(mesh="host:2")``. On a mesh of more than one device the
dispatch table has no ``pallas`` tier (``kernels.table_for_mesh``); the
resolutions printed show it.

Data is random, made from ``--seed``. Step times are smoke timings, not
benchmark numbers. The last line of standard output is one JSON object,
printed only when every phase and comparison passed; otherwise the
script exits non-zero without it. It exits non-zero at once when JAX
finds no TPU.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp

# the program, from the checkout this script sits in
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import repro  # noqa: E402
from repro.data import synthetic_graph  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.optim import adam_init, adam_update  # noqa: E402
from repro.relational import gcn_conv, rel_linear  # noqa: E402
from repro.relational.gcn import gcn_square_loss, partitioned_edges  # noqa: E402

LOGREG_SQL = """
mm   := SELECT Rx.row, SUM(multiply(Rx.val, theta.val))
        FROM Rx, theta WHERE Rx.col = theta.col GROUP BY Rx.row;
pred := SELECT mm.row, logistic(mm.val) FROM mm;
SELECT SUM(xent(pred.val, Ry.val)) FROM pred, Ry WHERE pred.row = Ry.row
"""

#: relative error bound (max |got - want| over max |want|, per output)
#: where both sides take their products at the same precision: they then
#: differ in summation order only, far below it; a wrong kernel or plan
#: lands far above.
RTOL = 1e-3

#: the bound where one side's dense products take one bf16 pass (8
#: significant bits) and the other's are f32, or are not rounded alike
#: (XLA may reduce a matrix-vector product in f32 where the Pallas
#: matmul rounds): logistic regression's sit near 2.4e-3 on a v5e; a
#: wrong kernel or plan still lands far above.
ONE_PASS_RTOL = 1e-2

#: the dispatch ops whose sites must resolve to the hardware kernels.
KERNEL_OPS = ("segment_sum", "gather_join", "blocked_matmul")

LOGREG_SHAPE = {"n": 524_288, "m": 1_024}
ARXIV_SHAPE = {
    "nodes": 169_343, "edges": 1_166_243, "feat": 128, "classes": 40,
    "hidden": 256,
}
TIMED_STEPS = 3


def _log(msg: str) -> None:
    print(msg, flush=True)


def _rel_err(got, want) -> float:
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    scale = float(jnp.max(jnp.abs(want)))
    return float(jnp.max(jnp.abs(got - want))) / (scale if scale else 1.0)


def _compare(phase: str, pairs, bound: float = RTOL) -> list:
    """Relative error of each (name, got, want); the names over ``bound``."""
    bad = []
    for name, got, want in pairs:
        err = _rel_err(got, want)
        ok = err <= bound
        _log(f"  [{phase}] {name}: rel err {err:.3e} (bound {bound:.0e}) "
             f"{'ok' if ok else 'FAIL'}")
        if not ok:
            bad.append(f"{phase}:{name}")
    return bad


def _kernel_sites(resolutions) -> dict:
    return {
        k: v for k, v in sorted(resolutions.items())
        if k.split("[", 1)[0] in KERNEL_OPS
    }


def _check_tiers(phase: str, resolutions, tier: str) -> list:
    """Print the kernel sites; the names of those not all on ``tier``.
    A site maps to one tier (a handle's step) or to the tiers of every
    step that shares it (``Database.resolutions()``)."""
    sites = {
        k: (v,) if isinstance(v, str) else tuple(v)
        for k, v in _kernel_sites(resolutions).items()
    }
    for k, v in sites.items():
        _log(f"  [{phase}] {k} -> {'/'.join(v)}")
    if not sites:
        return [f"{phase}:no kernel sites"]
    return [f"{phase}:{k}={'/'.join(v)}" for k, v in sites.items()
            if set(v) != {tier}]


def _timed(fn, *args):
    """(result, seconds) of ``fn(*args)`` run to completion."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def _report_steps(phase: str, first_s: float, step_s: list) -> None:
    _log(f"  [{phase}] first step (compile + run): {first_s:.3f} s")
    _log(f"  [{phase}] step seconds (smoke timing, not a benchmark): "
         + ", ".join(f"{s:.4f}" for s in step_s))
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        _log(f"  [{phase}] peak_bytes_in_use so far: "
             f"{stats['peak_bytes_in_use']}")


# ---------------------------------------------------------------------------
# logistic regression
# ---------------------------------------------------------------------------


def _logreg_data(n: int, m: int, seed: int):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k1, (n, m), jnp.float32)
    y = (x @ jax.random.normal(k2, (m,)) > 0).astype(jnp.float32)
    theta = jax.random.normal(k3, (m,), jnp.float32) * 0.01
    return x, y, theta


def _logreg_reference(x, y, theta):
    def loss(t, x, y):
        yhat = jax.nn.sigmoid(x @ t)
        return jnp.sum(-y * jnp.log(yhat) + (y - 1.0) * jnp.log1p(-yhat))

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss))(theta, x, y)


def logreg_phase(n: int, m: int, seed: int, *, dispatch=None,
                 tier: str = "pallas") -> list:
    """Train logistic regression through ``db.sql``; returns failures."""
    phase = "logreg"
    x, y, theta = _logreg_data(n, m, seed)
    db = repro.Database(dispatch=dispatch)
    db.put("Rx", x, keys=("row", "col"))
    db.put("Ry", y, keys=("row",))
    db.put("theta", theta, keys=("col",))
    handle = db.sql(LOGREG_SQL, wrt=("theta",))
    lr = 1.0 / n  # the loss sums over n rows

    step_s = []
    first_s = 0.0
    for i in range(1 + TIMED_STEPS):
        theta = db.get("theta").data
        (loss, grads), secs = _timed(handle.step)
        if i == 0:
            first_s = secs
        else:
            step_s.append(secs)
        db.put("theta", theta - lr * grads["theta"].data)
    _report_steps(phase, first_s, step_s)

    ref_loss, ref_grad = _logreg_reference(x, y, theta)
    bad = _compare(phase, [
        ("loss", loss.data, ref_loss),
        ("dtheta", grads["theta"].data, ref_grad),
    ], ONE_PASS_RTOL)
    return bad + _check_tiers(phase, handle.resolutions, tier)


# ---------------------------------------------------------------------------
# two-layer GCN
# ---------------------------------------------------------------------------


def _gcn_params(seed: int, feat: int, hidden: int, classes: int):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    return {
        "w1": jax.random.normal(k1, (feat, hidden)) * feat ** -0.5,
        "w2": jax.random.normal(k2, (hidden, classes)) * hidden ** -0.5,
    }


def _xent(logits, y):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _gcn_reference(params, x, keys, w, y):
    """The pure-JAX GCN: gather by src, f32 scatter-add by dst, dense
    products at the ambient matmul precision."""
    def loss(p, x, keys, w, y):
        src, dst = keys[:, 0], keys[:, 1]

        def conv(h):
            return jnp.zeros_like(h).at[dst].add(w[:, None] * h[src])

        h = jax.nn.relu(conv(x) @ p["w1"])
        return _xent(conv(h) @ p["w2"], y)

    return jax.jit(jax.value_and_grad(loss))(params, x, keys, w, y)


def gcn_phase(nodes: int, edges: int, feat: int, classes: int, hidden: int,
              seed: int, *, dispatch=None, tier: str = "pallas") -> list:
    """Train the GCN through the relational ops; returns failures."""
    phase = "gcn"
    g = synthetic_graph(nodes, edges, feat, classes, seed=seed)
    keys, w, x, y = g["edge_keys"], g["edge_w"], g["x"], g["y"]
    _log(f"  [{phase}] |V|={nodes} |E|={keys.shape[0]} (with self loops) "
         f"feat={feat} hidden={hidden} classes={classes}")
    db = repro.Database(dispatch=dispatch)
    db.put("Edge", repro.CooRelation(keys, w, (nodes, nodes)),
           keys=("src", "dst"))

    def loss_fn(p, x, keys, w, y):
        h = jax.nn.relu(rel_linear(gcn_conv(x, keys, w), p["w1"]))
        return _xent(rel_linear(gcn_conv(h, keys, w), p["w2"]), y)

    @jax.jit
    def step(p, opt, x, keys, w, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, keys, w, y)
        p, opt = adam_update(p, grads, opt, lr=0.01)
        return p, opt, loss, grads

    params = _gcn_params(seed, feat, hidden, classes)
    opt = adam_init(params)
    step_s = []
    first_s = 0.0
    with db.activate():
        for i in range(1 + TIMED_STEPS):
            before = params
            (params, opt, loss, grads), secs = _timed(
                step, params, opt, x, keys, w, y
            )
            if i == 0:
                first_s = secs
            else:
                step_s.append(secs)
    _report_steps(phase, first_s, step_s)

    ref_loss, ref_grads = _gcn_reference(before, x, keys, w, y)
    bad = _compare(phase, [
        ("loss", loss, ref_loss),
        ("dw1", grads["w1"], ref_grads["w1"]),
        ("dw2", grads["w2"], ref_grads["w2"]),
    ])
    return bad + _check_tiers(phase, db.resolutions(), tier)


# ---------------------------------------------------------------------------
# the four-chip path: sharded steps against one device
# ---------------------------------------------------------------------------


def _leaves(out):
    loss, grads = out
    vals = [("loss", loss.data)]
    for name, g in sorted(grads.items()):
        vals.append((f"d{name}", g.values if hasattr(g, "values") else g.data))
    return vals


def _mesh_vs_one(phase: str, build, wrt, mesh, tiers, bound) -> list:
    """Step ``build(mesh)``'s handle and ``build(None)``'s (one warm-up
    and the timed steps each); compare the two within ``bound``, and
    check each one's kernel tiers against ``tiers[0]`` (one device) and
    ``tiers[1]`` (the mesh). Returns failures."""
    bad, outs = [], []
    for label, m, tier in (("one device", None, tiers[0]),
                           ("mesh", mesh, tiers[1])):
        handle = build(m)
        out, first_s = _timed(lambda: handle.step(wrt=wrt))
        step_s = [_timed(lambda: handle.step(wrt=wrt))[1]
                  for _ in range(TIMED_STEPS)]
        _report_steps(f"{phase} / {label}", first_s, step_s)
        _log(f"  [{phase} / {label}] placements: {handle.placements}")
        bad += _check_tiers(f"{phase} / {label}", handle.resolutions, tier)
        outs.append(_leaves(out))
    return bad + _compare(phase, [
        (name, got, want) for (name, want), (_, got) in zip(*outs)
    ], bound)


def sharded_phase(chips: int, seed: int, *, arxiv=ARXIV_SHAPE,
                  logreg=LOGREG_SHAPE, dispatch=None,
                  tier: str = "pallas") -> list:
    """GCN (edge nnz rows sharded ``chips`` ways) and logreg (2 × 2 mesh)
    steps, each against the same step on one device; returns failures.
    On the mesh the ``pallas`` tier gives way to ``jnp``: the GCN query
    has no dense product, logreg's products need not round alike."""
    tiers = (tier, "jnp" if tier == "pallas" else tier)
    a = arxiv
    g = synthetic_graph(a["nodes"], a["edges"], a["feat"], a["classes"], seed=seed)
    edge = partitioned_edges(g["edge_keys"], g["edge_w"], a["nodes"], chips)
    query = gcn_square_loss(a["nodes"])

    def gcn(mesh):
        db = repro.Database(mesh=mesh, dispatch=dispatch)
        db.put("Edge", edge)
        db.put("Node", g["x"], keys=("node",))
        return db.query(query)

    bad = _mesh_vs_one("gcn nnz-sharded", gcn, ("Edge", "Node"),
                       make_host_mesh(model=1), tiers, RTOL)
    del edge, g
    gc.collect()

    x, y, theta = _logreg_data(logreg["n"], logreg["m"], seed)

    def logreg_handle(mesh):
        db = repro.Database(mesh=mesh, dispatch=dispatch)
        db.put("Rx", x, keys=("row", "col"))
        db.put("Ry", y, keys=("row",))
        db.put("theta", theta, keys=("col",))
        return db.sql(LOGREG_SQL, wrt=("theta",))

    return bad + _mesh_vs_one("logreg", logreg_handle, ("theta",),
                              "host:2", tiers, ONE_PASS_RTOL)


# ---------------------------------------------------------------------------


def _phase(name: str, fn, *args, **kwargs) -> list:
    """Run one phase; an exception is printed and becomes a failure, so
    the later phases still run and the script still exits non-zero."""
    _log(f"=== {name} ===")
    t0 = time.perf_counter()
    try:
        bad = fn(*args, **kwargs)
    except Exception:
        traceback.print_exc()
        bad = [f"{name}: raised"]
    _log(f"=== {name}: {'ok' if not bad else 'FAILED'} "
         f"({time.perf_counter() - t0:.1f} s) ===")
    gc.collect()
    return bad


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip path (default: 1)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    count = len(jax.devices())
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {count} "
              "device(s)", file=sys.stderr)
        return 2

    _log(f"device: {dev.device_kind} x{count}; "
         f"compile cache: {enable_compile_cache()}")
    failures = []
    if args.chips == 1:
        failures += _phase("logreg", logreg_phase, seed=args.seed,
                           **LOGREG_SHAPE)
        failures += _phase("gcn", gcn_phase, seed=args.seed, **ARXIV_SHAPE)
    else:
        failures += _phase("sharded", sharded_phase, args.chips, args.seed)
    if failures:
        print("chip_smoke: FAILED: " + "; ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
