"""Benchmark harness — one module per paper table/figure.

  twinsearch_bench   Figures 2-5 (running time, user/item x ML/Douban)
  setsize_bench      Sec 3.2 |Set_0| / Gaussian-bound validation
  scaling_bench      Sec 3.2 complexity model (k and n sweeps)
  kernel_bench       hot-spot micro-benchmarks
  maintenance_bench  burst-batched k-way merge-insert vs k sequential
                     inserts (bit-exactness asserted), k in {1,5,10,20,30}
  resilience_bench   fault-tolerance overhead: request-guard tax, arena
                     rotation vs fresh rebuild, sync-vs-incremental
                     rotation pause, health-check + snapshot
  recovery_bench     durability throughput: WAL append/group-commit cost,
                     serial vs batched replay, re-replication rows/s,
                     replica repair
  query_bench        batched read path: scalar loop vs batched vs fused
                     kernel vs server twin-dedup, twin-fraction sweep
                     (REPRO_BENCH_FAST=1 -> CI compile-check shapes)

Prints ``name,us_per_call,derived`` CSV.  Roofline terms for the full-scale
cells come from ``python -m repro.launch.dryrun --all`` +
``python -m benchmarks.roofline`` (no wall-clock on this CPU container).
"""
from __future__ import annotations

import argparse

from benchmarks.common import CSV
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=["twinsearch", "setsize", "scaling",
                                       "kernel", "maintenance",
                                       "resilience", "recovery", "query"],
                    default=None)
    args, _ = ap.parse_known_args()

    csv = CSV()
    csv.header()
    from benchmarks import (kernel_bench, maintenance_bench, query_bench,
                            recovery_bench, resilience_bench, scaling_bench,
                            setsize_bench, twinsearch_bench)
    todo = {
        "setsize": setsize_bench.main,
        "scaling": scaling_bench.main,
        "kernel": kernel_bench.main,
        "maintenance": maintenance_bench.main,
        "resilience": resilience_bench.main,
        "recovery": recovery_bench.main,
        "query": query_bench.main,
        "twinsearch": twinsearch_bench.main,
    }
    for name, fn in todo.items():
        if args.only and name != args.only:
            continue
        print(f"# --- {name} ---", flush=True)
        fn(csv)


if __name__ == "__main__":
    main()
