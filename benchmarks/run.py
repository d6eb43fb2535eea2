"""Benchmark harness entry point (deliverable d).

One module per paper table/figure (DESIGN.md §9):
  Fig 2 left/middle -> bench_mean_estimation     Fig 2 right -> bench_mp_comm
  Fig 3 left/middle -> bench_linclass            Fig 3 right -> bench_cl_comm
  Fig 5 (App. E)    -> bench_scalability
  kernels           -> bench_kernels             §Roofline   -> roofline

``python -m benchmarks.run`` prints ``name,us_per_call,derived`` CSV rows
(fast settings); ``--full`` approaches the paper-scale settings.
"""

from __future__ import annotations

import argparse
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args, _ = ap.parse_known_args()
    fast = not args.full

    from . import (bench_mean_estimation, bench_mp_comm, bench_linclass,
                   bench_cl_comm, bench_scalability, bench_kernels, roofline)
    suites = [
        ("mean_estimation", bench_mean_estimation.main),
        ("mp_comm", bench_mp_comm.main),
        ("linclass", bench_linclass.main),
        ("cl_comm", bench_cl_comm.main),
        ("scalability", bench_scalability.main),
        ("kernels", bench_kernels.main),
        ("roofline", roofline.main),
    ]
    failures = 0
    for name, fn in suites:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        print(f"### {name}", flush=True)
        try:
            fn(fast=fast)
            print(f"{name},{(time.time()-t0)*1e6:.0f},ok", flush=True)
        except Exception:
            traceback.print_exc()
            print(f"{name},,FAILED", flush=True)
            failures += 1
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
