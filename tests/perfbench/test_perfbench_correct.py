"""``correct`` comes out false where it should: for the control (the
reference in bfloat16 in the program's place) and, through a whole run
with the timed path broken underneath, for each fault a training cell
can have: a step that returns its state unchanged, and half of the batch
left out with the mean taken over the rest. The cells run on one chip,
so none has an exchange between chips to leave out."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest
from conftest import CELLS, REPO, run_cell

import repro.core.session as session
from perfbench import check, harness
from perfbench.feed import Feed
from repro.core.relation import DenseRelation


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, cell):
    c = harness.load_cell(tiny_root, cell, trace=False)
    limits = json.loads((REPO / "perfbench" / "cells" / f"{cell}.json").read_text())
    for seed in (1, 2, 3):
        feed = Feed(c.traffic, c.model.rows(c.cfg), seed)
        inputs = c.model.make_inputs(c.cfg, feed, seed)
        ref = c.reference.run(c.cfg, inputs)
        control = c.reference.run(c.cfg, inputs, dtype=jnp.bfloat16)
        ok, checks = check.judge(check.compare(control, ref), limits["limits"])
        assert not ok, checks
        ok, _ = check.judge(check.compare(ref, ref), limits["limits"])
        assert ok


def _unchanged_adam(params, grads, state, **kw):
    return params, state


def _gcn_half_xent(orig):
    def xent(logits, y):
        return orig(logits[::2], y[::2])
    return xent


def _logreg_unchanged(orig):
    def step(self, **kw):
        out, grads = orig(self, **kw)
        return out, {k: DenseRelation(jnp.zeros_like(g.data), g.key_arity)
                     for k, g in grads.items()}
    return step


def _logreg_half(orig):
    def step(self, **kw):
        db = self.db
        full = {n: db.get(n) for n in ("Rx", "Ry")}
        half = full["Rx"].data.shape[0] // 2
        for n, rel in full.items():
            db.put(n, DenseRelation(rel.data[:half], rel.key_arity))
        try:
            out, grads = orig(self, **kw)
        finally:
            for n, rel in full.items():
                db.put(n, rel)
        two = lambda r: DenseRelation(2 * r.data, r.key_arity)  # noqa: E731
        return two(out), {k: two(g) for k, g in grads.items()}
    return step


def _break(monkeypatch, cell, fault):
    if cell.startswith("gcn"):
        import perfbench.models.gcn as gcn

        if fault == "unchanged":
            monkeypatch.setattr(gcn, "adam_update", _unchanged_adam)
        else:
            monkeypatch.setattr(gcn, "_xent", _gcn_half_xent(gcn._xent))
    else:
        wrap = _logreg_unchanged if fault == "unchanged" else _logreg_half
        monkeypatch.setattr(session.QueryHandle, "step",
                            wrap(session.QueryHandle.step))


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_step_is_not_correct(tiny_root, monkeypatch, cell, fault):
    _break(monkeypatch, cell, fault)
    rc, res, err = run_cell(tiny_root, cell, seconds=0.1)
    assert rc == 0 and res["correct"] is False, err
    assert "FAIL" in err
    jax.clear_caches()
