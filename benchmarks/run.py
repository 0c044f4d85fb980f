"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Prints ``name,us_per_call,derived`` CSV rows (value semantics per bench:
microseconds for timing benches, counts for Table 1, MSE for Figure 3).
The roofline analysis (deliverable g) is its own module: benchmarks.roofline.
"""

import argparse
import sys

from repro import cache as cache_mod


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced iteration counts (CI)")
    ap.add_argument("--only", default=None,
                    help="comma list: fig2,table1,fig3,serve,kernels")
    args = ap.parse_args()
    cache_mod.configure_compile_cache()
    which = set((args.only or "fig2,table1,fig3").split(","))

    print("name,us_per_call,derived")
    if "fig2" in which:
        from benchmarks import bench_fig2_speed
        bench_fig2_speed.main(csv=True)
        sys.stdout.flush()
    if "table1" in which:
        from benchmarks import bench_table1_params
        bench_table1_params.main(csv=True)
        sys.stdout.flush()
    if "fig3" in which:
        from benchmarks import bench_fig3_recovery
        bench_fig3_recovery.main(csv=True, steps=300 if args.quick else 3000)
        sys.stdout.flush()
    if "serve" in which:
        from benchmarks import bench_serve
        bench_serve.main(csv=True, argv=[])
        sys.stdout.flush()
    if "kernels" in which:
        from benchmarks import bench_kernels
        bench_kernels.main(csv=True, argv=["--quick"] if args.quick else [])
        sys.stdout.flush()


if __name__ == "__main__":
    main()
