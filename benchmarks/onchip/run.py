#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/onchip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout, on a machine that holds the chips the cell
asks for.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a profiled window.  The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` a ``breakdown``,
and last ``checks``: each number compared beside its limit); the last
lines of standard error repeat the compared numbers.  With ``--trace 1``
every device-trace number is read over the part of the window that the
profiler kept: ``device`` gives ``busy_s`` and ``window_s`` over that
part and ``traced_share``, that part over the whole window, and the
``per-layer counts:`` line the steps counted beside the step programs'
runs in the trace (``decode_steps`` / ``decode_modules``, ``prefills`` /
``prefill_modules``) and those the host dispatched.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))                 # the onchip package
sys.path.insert(0, str(HERE.parents[1] / "src"))     # the system under test


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from onchip import harness
    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    cache = harness.setup_compile_cache(cell.root)
    print(f"workload={cell.name} seed={args.seed} seconds={args.seconds:g} "
          f"trace={args.trace} device_kind={devices[0].device_kind} "
          f"chips={len(devices)} compile_cache={cache}", flush=True)
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T_PROC0, log=lambda s: print(s, flush=True))
    for name, c in out["checks"].items():
        print(f"{name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    checks = out.pop("checks")
    out["checks"] = checks                           # last key
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
