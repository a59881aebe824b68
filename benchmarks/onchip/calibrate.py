#!/usr/bin/env python3
"""Readings that the limit of a cell's comparison is set from, in one
process on the chip at the cell's own size and load.

    python3 benchmarks/onchip/calibrate.py --workload <name> \\
        --seconds <s> --seeds 1,2,3,...

For each seed, one whole benchmark run (``harness.run``: weights, the
latency fit, the engine and its warm-up from that seed, the cell's
traffic through a ramp, a window of ``--seconds`` and the drain), whose
check also reads the fp8 control in the program's place on the same
sample.  One JSON line per seed: the program's widest gap (``gap``) and
the control's (``control_gap``, the gap of the token the fp8 pass puts
first).  The lower reading is the largest ``gap`` over the seeds, the
upper the smallest ``control_gap``.  Not part of a benchmark run.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()

    from onchip import harness
    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    harness.setup_compile_cache(cell.root)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run(cell, seed, args.seconds, False, devices, t0,
                          log=lambda s: print(s, file=sys.stderr,
                                              flush=True),
                          control=True)
        checks = out["checks"]
        print(json.dumps({
            "seed": seed, "gap": checks["logit_gap"]["value"],
            "control_gap": out["control_gap"],
            "unfinished": checks["unfinished"]["value"],
            **out["compared"], "attempted": out["attempted"],
            "failed": out["failed"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "run_s": time.perf_counter() - t0}), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
