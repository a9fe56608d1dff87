"""``correct`` comes out false where it should: for the control (the
reference in bfloat16 in the program's place) and, through a whole run
with the timed path broken underneath, for each fault that the cell's
model can have (``models/<model>.py``: ``FAULTS``, ``fault``): a step
that returns its state unchanged, half of the batch left out with the
mean taken over the rest, and, on several chips, the exchange between
them left out."""

from __future__ import annotations

import pytest
from conftest import CELLS, REPO, assert_control_fails, assert_fault_fails, faults


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_root, cell):
    assert_control_fails(tiny_root, cell)


@pytest.mark.parametrize("cell, fault", [(c, f) for c in CELLS for f in faults(REPO, c)])
def test_broken_step_is_not_correct(tiny_root, cell, fault):
    assert_fault_fails(tiny_root, cell, fault)
