"""Benchmark harness — one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--quick]``
Prints ``name,us_per_call,derived`` CSV for every benchmark.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced sweeps (CI mode)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from benchmarks import (bench_annealing_params, bench_fit,
                            bench_goodput, bench_kernels,
                            bench_latency_pred, bench_move_ablation,
                            bench_online, bench_output_pred,
                            bench_overall, bench_overhead, bench_scaling,
                            bench_serving)
    suites = {
        "fig7_overall": bench_overall.main,
        "table1_overhead": bench_overhead.main,
        "fig8_annealing_params": bench_annealing_params.main,
        "fig9_output_pred": bench_output_pred.main,
        "fig10_latency_pred": bench_latency_pred.main,
        "fig11_scaling": bench_scaling.main,
        "table2_fit": bench_fit.main,
        "kernels": bench_kernels.main,
        "move_ablation": bench_move_ablation.main,
        "online": bench_online.main,
        "serving": bench_serving.main,
        "goodput": bench_goodput.main,
    }
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        try:
            fn(quick=args.quick)
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
