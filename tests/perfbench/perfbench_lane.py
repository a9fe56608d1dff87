"""What the benchmark's CPU tests do with one cell, in a process that
holds as many devices as the cell has chips. ``conftest.in_lane`` calls
these functions in the test's own process for a one-chip cell, and for
a cell on more chips starts

    python perfbench_lane.py <root> <function> <keyword arguments as JSON>

with ``JAX_PLATFORMS=cpu`` and that many virtual CPU devices, the copy of
the benchmark at ``<root>`` first on the path; the last line of its
standard output is the function's result as JSON."""

from __future__ import annotations

import contextlib
import io
import json
import pathlib
import sys


def run(root, cell: str, seed: int, seconds: float, trace: bool,
        fault=None) -> list:
    """One run of ``cell`` (``harness.run``, no look for a chip), with
    the model's ``fault`` entered where one is named: ``[exit code,
    result line or None, standard error]``. What the program prints to
    standard error is in the last."""
    from perfbench import harness

    root = pathlib.Path(root)
    out, err = io.StringIO(), io.StringIO()
    broken = (harness.load_cell(root, cell, trace).model.fault(fault)
              if fault else contextlib.nullcontext())
    with broken, contextlib.redirect_stderr(err):
        rc = harness.run(cell, seed, seconds, trace, root=root,
                         check_device=False, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return [rc, json.loads(lines[-1]) if lines else None, err.getvalue()]


def faults(root, cell: str) -> list:
    """The faults that the model of ``cell`` can be broken by."""
    from perfbench import harness

    return list(harness.load_cell(pathlib.Path(root), cell, False).model.FAULTS)


def control(root, cell: str, seeds) -> list:
    """Per seed, ``[control correct, reference correct against itself,
    the control's checks]``: the reference in bfloat16 in the program's
    place, judged by the cell's limits against the reference."""
    import jax
    import jax.numpy as jnp

    from perfbench import check, harness
    from perfbench.feed import Feed

    c = harness.load_cell(pathlib.Path(root), cell, trace=False)
    devices = jax.devices()[:c.chips]
    rows = []
    for seed in seeds:
        feed = Feed(c.traffic, c.model.rows(c.cfg), seed)
        inputs = c.model.make_inputs(c.cfg, feed, seed, devices)
        ref = c.reference.run(c.cfg, inputs)
        low = c.reference.run(c.cfg, inputs, dtype=jnp.bfloat16)
        ok, checks = check.judge(check.compare(low, ref), c.limits)
        self_ok, _ = check.judge(check.compare(ref, ref), c.limits)
        rows.append([ok, self_ok, checks])
    return rows


def main(argv) -> int:
    root, what, kwargs = argv[1], argv[2], json.loads(argv[3])
    result = {"run": run, "faults": faults, "control": control}[what](root, **kwargs)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
