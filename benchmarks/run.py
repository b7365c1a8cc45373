"""Benchmark entry point: one function per paper table/figure.

Prints ``name,value,derived`` CSV.  Sections:
  fig2a/2b/2c, fig4, fig5, fig6   — paper-figure reproductions (simulated
                                    wall-clock seconds to target accuracy)
  kernel/*                        — kernel micro-benchmarks + structural
                                    roofline accounting
  roofline/*                      — per (arch x shape) roofline terms from
                                    the multi-pod dry-run artifacts
  ingest/* + dispatch/* + tuner/* — wire-path + autotune-sweep benchmarks
                                    (--only wire): the subset CI's
                                    regression gate runs; both local runs
                                    and the `ingest-bench` job go through
                                    this one entrypoint so their numbers
                                    come from the same code path
  fleet/*                         — cohort fleet-size sweep (--only fleet):
                                    server resident state + per-round wall
                                    clock vs 10^2..10^5 simulated clients,
                                    gated by benchmarks/compare.py
  sched/*                         — availability x scheduler TTA sweep
                                    (--only sched): three churn scenarios
                                    x three dispatch policies, gated by
                                    benchmarks/compare.py (rate_staleness
                                    must beat random on every scenario)

Usage: PYTHONPATH=src python -m benchmarks.run \
           [--only figs|kernels|roofline|wire|fleet|sched]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=["figs", "kernels", "roofline", "wire",
                                       "fleet", "sched"],
                    default=None)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,value,derived")

    t0 = time.time()
    if args.only == "fleet":
        from benchmarks.fleet_bench import bench_fleet
        try:
            for name, value, derived in bench_fleet():
                print(f"{name},{value},{derived}", flush=True)
        except Exception as e:
            traceback.print_exc()
            print(f"bench_fleet,ERROR,{type(e).__name__}", flush=True)
            sys.exit(1)       # the fleet gate depends on this report
        print(f"total_benchmark_wall_seconds,{time.time() - t0:.1f},",
              flush=True)
        return
    if args.only == "sched":
        from benchmarks.sched_bench import bench_sched
        try:
            for name, value, derived in bench_sched():
                print(f"{name},{value},{derived}", flush=True)
        except Exception as e:
            traceback.print_exc()
            print(f"bench_sched,ERROR,{type(e).__name__}", flush=True)
            sys.exit(1)       # the scheduler gate depends on this report
        print(f"total_benchmark_wall_seconds,{time.time() - t0:.1f},",
              flush=True)
        return
    if args.only == "wire":
        from benchmarks.kernel_bench import (
            bench_dispatch, bench_ingest, bench_kernel_sweep,
        )
        failed = False
        for bench in (bench_ingest, bench_dispatch, bench_kernel_sweep):
            try:
                for name, value, derived in bench():
                    print(f"{name},{value},{derived}", flush=True)
            except Exception as e:
                traceback.print_exc()
                print(f"{bench.__name__},ERROR,{type(e).__name__}",
                      flush=True)
                failed = True
        print(f"total_benchmark_wall_seconds,{time.time() - t0:.1f},",
              flush=True)
        if failed:
            sys.exit(1)       # a broken bench must fail the CI gate loudly
        return
    if args.only in (None, "figs"):
        from benchmarks.paper_figs import ALL_FIGS
        for fig in ALL_FIGS:
            try:
                for name, value, derived in fig():
                    print(f"{name},{value},{derived}", flush=True)
            except Exception as e:
                traceback.print_exc()
                print(f"{fig.__name__},ERROR,{type(e).__name__}", flush=True)

    if args.only in (None, "kernels"):
        from benchmarks.kernel_bench import ALL_KERNEL_BENCHES
        for bench in ALL_KERNEL_BENCHES:
            try:
                for name, value, derived in bench():
                    print(f"{name},{value},{derived}", flush=True)
            except Exception as e:
                traceback.print_exc()
                print(f"{bench.__name__},ERROR,{type(e).__name__}", flush=True)

    if args.only in (None, "roofline"):
        try:
            from benchmarks.roofline import csv_rows
            for name, value, derived in csv_rows():
                print(f"{name},{value},{derived}", flush=True)
        except Exception as e:
            traceback.print_exc()
            print(f"roofline,ERROR,{type(e).__name__}", flush=True)

    print(f"total_benchmark_wall_seconds,{time.time() - t0:.1f},",
          flush=True)


if __name__ == "__main__":
    main()
