"""Benchmark driver — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--fast]``
prints ``name,us_per_call,derived`` CSV rows (also saved to
benchmarks/results.csv).
"""

from __future__ import annotations

import argparse
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true", help="smaller sweeps")
    ap.add_argument("--only", default=None, help="comma list of module names")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_async_service,
        bench_audit,
        bench_cluster,
        bench_dbindex_eagr,
        bench_iindex,
        bench_kernels,
        bench_mc_emc,
        bench_multiquery,
        bench_nonindex_gap,
        bench_obs_overhead,
        bench_scalability,
        bench_service,
        bench_updates,
        bench_window_algebra,
    )
    from benchmarks.common import flush_csv

    t0 = time.time()
    print("name,us_per_call,derived")
    mods = {
        "mc_emc": lambda: bench_mc_emc.run(n=8_000 if args.fast else 20_000,
                                           hops=(1, 2) if args.fast else (1, 2, 3)),
        "dbindex_eagr": lambda: bench_dbindex_eagr.run(n=800 if args.fast else 2000),
        "scalability": bench_scalability.run if not args.fast else (lambda: None),
        "iindex": lambda: bench_iindex.run(fast=args.fast),
        "nonindex_gap": lambda: bench_nonindex_gap.run(n=5_000 if args.fast else 8_000),
        "kernels": bench_kernels.run,
        "updates": lambda: bench_updates.run(n=20_000 if args.fast else 100_000),
        "multiquery": lambda: bench_multiquery.run(n=8_000 if args.fast else 20_000),
        "service": lambda: bench_service.run(smoke=args.fast),
        "async_service": lambda: bench_async_service.run(smoke=args.fast),
        "window_algebra": lambda: bench_window_algebra.run(
            n=4_000 if args.fast else 20_000),
        "obs_overhead": lambda: bench_obs_overhead.run(smoke=args.fast),
        "audit": lambda: bench_audit.run(smoke=args.fast),
        "cluster": lambda: bench_cluster.run(smoke=args.fast),
    }
    # bench_sharded_stream is deliberately NOT in this table: it must force
    # the host-platform device count before jax initializes, so it runs
    # standalone (`python -m benchmarks.bench_sharded_stream`, see the
    # sharded CI job).
    only = set(args.only.split(",")) if args.only else None
    for name, fn in mods.items():
        if only and name not in only:
            continue
        print(f"# --- {name} ---")
        fn()
    flush_csv("benchmarks/results.csv")
    print(f"# total {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
