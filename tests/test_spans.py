"""The program's host spans (``repro.core.spans``): a session step under
the profiler records each span, nested as the layers call each other,
and the lowering and planning spans on the first step only."""

from __future__ import annotations

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import spans

LOGREG_SQL = """
mm   := SELECT Rx.row, SUM(multiply(Rx.val, theta.val))
        FROM Rx, theta WHERE Rx.col = theta.col GROUP BY Rx.row;
pred := SELECT mm.row, logistic(mm.val) FROM mm;
SELECT SUM(xent(pred.val, Ry.val)) FROM pred, Ry WHERE pred.row = Ry.row
"""


def _program_spans(trace_dir):
    """``[(name, start, end)]`` of the program's spans, by start."""
    (path,) = pathlib.Path(trace_dir).rglob("*.xplane.pb")
    profile = jax.profiler.ProfileData.from_file(str(path))
    out = [(ev.name, ev.start_ns, ev.end_ns)
           for plane in profile.planes if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith(spans.PREFIX)]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _within(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Two logistic-regression steps through the session, each putting
    its rows and its parameters, under the profiler."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=64), jnp.float32)
    theta = jnp.zeros(8, jnp.float32)
    db = repro.Database()
    db.put("Rx", x, keys=("row", "col"))
    db.put("Ry", y, keys=("row",))
    db.put("theta", theta, keys=("col",))
    handle = db.sql(LOGREG_SQL, wrt=("theta",))
    tdir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(tdir)):
        for _ in range(2):
            db.put("Rx", x, keys=("row", "col"))
            db.put("Ry", y, keys=("row",))
            _, grads = handle.step()
            theta = theta - 0.1 * grads["theta"].data
            db.put("theta", theta, keys=("col",))
        jax.block_until_ready(theta)
    return _program_spans(tdir)


def test_names_are_constants_under_the_prefix():
    assert len(set(spans.NAMES)) == len(spans.NAMES) == 8
    assert all(n.startswith(spans.PREFIX) for n in spans.NAMES)


def test_step_records_every_span(recorded):
    assert {n for n, _, _ in recorded} == set(spans.NAMES)
    by = {n: [s for s in recorded if s[0] == n] for n in spans.NAMES}
    assert len(by[spans.SESSION_PUT]) == 6
    assert len(by[spans.SESSION_STEP]) == 2


@pytest.mark.parametrize("child, parent", [
    (spans.SESSION_STATS, spans.SESSION_PUT),
    (spans.ENGINE_LOOKUP, spans.SESSION_STEP),
    (spans.ENGINE_LOWER, spans.ENGINE_LOOKUP),
    (spans.ENGINE_PLAN, spans.ENGINE_LOOKUP),
    (spans.ENGINE_CALL, spans.SESSION_STEP),
    (spans.ENGINE_DISPATCH, spans.ENGINE_CALL),
])
def test_spans_nest_as_the_layers_call(recorded, child, parent):
    parents = [s for s in recorded if s[0] == parent]
    children = [s for s in recorded if s[0] == child]
    assert children
    for c in children:
        assert any(_within(c, p) for p in parents), (c, parents)


def test_lowering_and_planning_on_the_first_step_only(recorded):
    first, second = [s for s in recorded if s[0] == spans.SESSION_STEP]
    for name in (spans.ENGINE_LOWER, spans.ENGINE_PLAN):
        (only,) = [s for s in recorded if s[0] == name]
        assert _within(only, first) and not _within(only, second)
    for name in (spans.ENGINE_LOOKUP, spans.ENGINE_CALL, spans.ENGINE_DISPATCH):
        assert sum(_within(s, second) for s in recorded if s[0] == name) == 1
