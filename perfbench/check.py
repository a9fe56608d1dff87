"""The comparison that decides ``correct`` for a training cell.

The program and the plain reference each give, for the first steps of
one run (``STEPS``), the readings of ``readings()``: each step's loss,
the norm of each leaf of the first gradient as the optimizer got it, and
the norm of each leaf's change over the steps. Three numbers compare
them, each against the limit of its cell (``cells/<cell>.json``):

* ``loss``: the largest relative gap of a step's loss;
* ``grad``: the worst leaf's gap between the two first-gradient norms,
  over the reference's norm of that leaf or of the median leaf,
  whichever is larger (some gradients are all but zero);
* ``change``: the same for the norms of the parameters' change, over
  the leaves that the reference's gradient moves: a leaf whose first
  gradient is under ``STILL`` of the median leaf's moves by round-off
  alone under Adam and is left out.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

#: steps that set-up drives and the reference follows.
STEPS = 3

#: a leaf whose reference gradient norm is under this share of the
#: median leaf's moves by round-off alone.
STILL = 1e-3

NUMBERS = ("loss", "grad", "change")


def norm(x) -> float:
    return float(np.linalg.norm(np.asarray(x, np.float64).ravel()))


def readings(losses, grads: dict, changes: dict) -> dict:
    """The readings of one side: ``STEPS`` losses, and per leaf the norm
    of the first gradient and of the change over ``STEPS`` steps."""
    if len(losses) != STEPS:
        raise ValueError(f"{len(losses)} losses for {STEPS} steps")
    return {
        "loss": [float(np.asarray(v, np.float64)) for v in losses],
        "grad": {k: norm(v) for k, v in grads.items()},
        "change": {k: norm(v) for k, v in changes.items()},
    }


def _rel(got: float, want: float, scale: float) -> float:
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / scale if scale else (0.0 if got == want else math.inf)


def compare(prog: dict, ref: dict) -> dict:
    """``{number: value}`` of the program's readings against the
    reference's."""
    loss = max(_rel(p, r, abs(r)) for p, r in zip(prog["loss"], ref["loss"]))
    g_med = statistics.median(ref["grad"].values())
    grad = max(
        _rel(prog["grad"][k], r, max(r, g_med)) for k, r in ref["grad"].items()
    )
    moving = [k for k, r in ref["grad"].items() if r >= STILL * g_med]
    c_med = statistics.median(ref["change"][k] for k in moving)
    change = max(
        _rel(prog["change"][k], ref["change"][k], max(ref["change"][k], c_med))
        for k in moving
    )
    return {"loss": loss, "grad": grad, "change": change}


def judge(values: dict, limits: dict) -> tuple:
    """(correct, ``{number: {"value", "limit"}}``): correct when every
    number is at or under its limit (NaN never is)."""
    checks = {
        k: {"value": values[k], "limit": float(limits[k])} for k in NUMBERS
    }
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
