"""Benchmark driver: one benchmark per paper table/figure.

  logreg           §2.3 running example — RA-autodiff overhead vs jax.grad
  gcn              Tables 2–3 — GCN per-epoch, mini-batch + full-graph
  nnmf             Figure 2 — non-negative matrix factorization per-epoch
  kge              Figure 3 — TransE/TransR 100-iteration time
  rjp_ablation     §4 — RJP optimizations on/off
  engine_overhead  staged engine: eager re-lowering vs cached Compiled
  kernel_dispatch  dispatch tiers: jnp vs ref (vs pallas on TPU), raw
                   kernels + compiled logreg/GCN grad steps
  coo_scale        COO nnz sharding: replicated vs nnz-sharded GCN grad
                   step, per-device edge-relation bytes (needs >=2
                   devices for the sharded lane to differ)
  oocore_scale     out-of-core streaming: GCN grad step with the edge
                   relation >=4x past the simulated device-memory
                   budget, chunk waves vs the in-core oracle
  serving_load     async serving front door: open-loop concurrent
                   single-row requests through db.endpoint, sustained
                   QPS + p50/p99 latency (continuous batching on)

Each suite's rows are also written to BENCH_<suite>.json.

Usage:  PYTHONPATH=src python -m benchmarks.run [name ...]
"""

import sys

from .common import ROWS, emit_header, emit_json


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        coo_scale,
        engine_overhead,
        gcn,
        kernel_dispatch,
        kge,
        logreg,
        nnmf,
        oocore_scale,
        rjp_ablation,
        serving_load,
    )

    suites = {
        "logreg": logreg.run,
        "gcn": gcn.run,
        "nnmf": nnmf.run,
        "kge": kge.run,
        "rjp_ablation": rjp_ablation.run,
        "engine_overhead": engine_overhead.run,
        "kernel_dispatch": kernel_dispatch.run,
        "coo_scale": coo_scale.run,
        "oocore_scale": oocore_scale.run,
        "serving_load": serving_load.run,
    }
    names = sys.argv[1:] or list(suites)
    unknown = [n for n in names if n not in suites]
    if unknown:
        raise SystemExit(f"unknown benchmark(s) {unknown}; have {list(suites)}")
    emit_header()
    for n in names:
        print(f"# --- {n} ---")
        start = len(ROWS)
        suites[n]()
        emit_json(f"BENCH_{n}.json", ROWS[start:])


if __name__ == "__main__":
    main()
