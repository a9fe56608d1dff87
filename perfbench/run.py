"""Run one cell of the benchmark on the machine it is started on.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints one JSON result line last on standard output, and the numbers of
the comparison that decides ``correct`` last on standard error. Exits 2,
printing no result, when JAX finds no accelerator, fewer chips than the
cell asks for, or a device kind missing from ``perfbench/peaks.json``.
JAX's compile cache is kept at ``<checkout>/.jax_cache``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the cache directory is part of the cache's key: one fixed path,
    # inside the checkout, set before JAX reads it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from perfbench import harness

    return harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                       root=ROOT, t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
