"""Record a short traced window of one cell, for the tests of the trace
reduction (``perfbench/testdata/``).

    python perfbench/record_trace.py --workload <cell> --seed <n> --steps <k> --out <file.xplane.pb.gz>

Builds the cell's step as a run does (data and weights from the seed),
takes one step to compile it, then runs ``k`` steps under the profiler
with the benchmark's spans, as the traced window of a run does, and
writes the trace gzipped to ``--out``. Exits 2 without an accelerator.
"""

from __future__ import annotations

import argparse
import gzip
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]


def record(root: pathlib.Path, workload: str, seed: int, steps: int,
           out: pathlib.Path) -> int:
    """Record ``steps`` steps of ``workload`` to ``out``; returns the
    size of the file written, in bytes."""
    import jax

    from perfbench import harness
    from perfbench.feed import Feed

    cell = harness.load_cell(root, workload, trace=False)
    devices = jax.devices()[:cell.chips]
    spans = harness.Spans(True)
    feed = Feed(cell.traffic, cell.model.rows(cell.cfg), seed)
    inputs = cell.model.make_inputs(cell.cfg, feed, seed, devices)
    trainer = cell.model.Trainer(cell.cfg, feed, inputs, spans, devices)
    jax.block_until_ready(trainer.step(0))
    tdir = tempfile.mkdtemp(prefix="perfbench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        with spans("window"):
            prev = None
            for i in range(1, steps + 1):
                cur = trainer.step(i)
                if prev is not None:
                    with spans("wait"):
                        jax.block_until_ready(prev)
                prev = cur
            with spans("wait"):
                jax.block_until_ready(prev)
        jax.profiler.stop_trace()
        (trace,) = pathlib.Path(tdir).rglob("*.xplane.pb")
        out.parent.mkdir(parents=True, exist_ok=True)
        with gzip.open(out, "wb", compresslevel=9) as f:
            f.write(trace.read_bytes())
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    return out.stat().st_size


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--out", type=pathlib.Path, required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    if jax.devices()[0].platform == "cpu":
        print("record_trace: needs an accelerator; JAX found only the CPU",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    size = record(ROOT, args.workload, args.seed, args.steps, args.out)
    print(f"{args.out}: {args.steps} steps, {size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
