"""Readings from which a cell's limits are set (``cells/<cell>.json``).

    python perfbench/calibrate.py --workload <cell> --seeds 1,2,3

For each seed, in one process: the program's first ``check.STEPS`` steps
as a run drives them, then the reference at float32, and the numbers of
``check.compare`` against it for

* ``program``: the program (the lower reading is its largest over the
  seeds);
* ``control``: the reference in bfloat16, in the program's place;
* ``half_batch``: the reference with half of each step's rows left out
  and the mean taken over the rest.

A state left unchanged reads 1 on ``grad`` and ``change`` by
construction and needs no run. One JSON line per seed; nothing is timed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def readings(workload: str, seeds, root=ROOT, out=None) -> list:
    import jax
    import jax.numpy as jnp

    from perfbench import check, harness
    from perfbench.feed import Feed

    cell = harness.load_cell(root, workload, trace=False)
    devices = jax.devices()[:cell.chips]
    rows = []
    for seed in seeds:
        feed = Feed(cell.traffic, cell.model.rows(cell.cfg), seed)
        inputs = cell.model.make_inputs(cell.cfg, feed, seed, devices)
        trainer, prog = harness.first_steps(cell, feed, inputs,
                                            harness.Spans(False), devices)
        del trainer
        gc.collect()
        ref = cell.reference.run(cell.cfg, inputs)
        row = {"seed": seed, "program": check.compare(prog, ref)}
        row["control"] = check.compare(
            cell.reference.run(cell.cfg, inputs, dtype=jnp.bfloat16), ref)
        row["half_batch"] = check.compare(
            cell.reference.run(cell.cfg, inputs, fault="half_batch"), ref)
        rows.append(row)
        if out is not None:
            print(json.dumps(row), file=out, flush=True)
        del inputs
        gc.collect()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    readings(args.workload, [int(s) for s in args.seeds.split(",")],
             out=sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
