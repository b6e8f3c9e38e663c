"""Benchmark harness — one table per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV blocks per table (plus the richer
per-table CSVs each module emits).  Tables:

  speed_functions   paper Figs 1-6, 13-14  (backend performance profiles)
  pfft_speedups     paper Figs 15-24       (PFFT-FPM / -PAD / -CZT vs basic)
  partition_quality paper Figs 9-12        (HPOPTA vs load-balance)
  roofline          EXPERIMENTS.md §Roofline (from dry-run records)
  serve             DESIGN.md §Transform serving (continuous batching
                    of a Zipf request mix -> BENCH_serve.json)

NOTE: this container is one CPU core — the parallel-speedup component of
the paper's results needs >1 physical core; the padding/model components
reproduce directly (see EXPERIMENTS.md for the mapping).
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: speed,pfft,partition,roofline,serve")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import (partition_quality, pfft_speedup, roofline_report,
                            speed_functions)

    t_all = time.perf_counter()
    if only is None or "speed" in only:
        t0 = time.perf_counter()
        speed_functions.run(quick=args.quick)
        print(f"speed_functions,{(time.perf_counter() - t0) * 1e6:.0f},wall_us\n")
    if only is None or "pfft" in only:
        t0 = time.perf_counter()
        pfft_speedup.run(quick=args.quick)
        print(f"pfft_speedups,{(time.perf_counter() - t0) * 1e6:.0f},wall_us\n")
    if only is None or "partition" in only:
        t0 = time.perf_counter()
        partition_quality.run()
        print(f"partition_quality,{(time.perf_counter() - t0) * 1e6:.0f},wall_us\n")
    if only is None or "roofline" in only:
        t0 = time.perf_counter()
        roofline_report.run()
        print(f"roofline,{(time.perf_counter() - t0) * 1e6:.0f},wall_us\n")
    if only is None or "serve" in only:
        from benchmarks import serve_bench
        t0 = time.perf_counter()
        serve_bench.run(smoke=args.quick)
        print(f"serve,{(time.perf_counter() - t0) * 1e6:.0f},wall_us\n")
    print(f"benchmarks_total,{(time.perf_counter() - t_all) * 1e6:.0f},wall_us")
    return 0


if __name__ == "__main__":
    sys.exit(main())
