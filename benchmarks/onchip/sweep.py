#!/usr/bin/env python3
"""Find a cell's knee: its mix at several offered rates, in one process.

    python3 benchmarks/onchip/sweep.py --workload <name> --seed <n> \\
        --seconds <s> --rates 2,4,6,8

Sets the cell up once, then for each rate offers the mix (same seed,
that rate) through a ramp, a window of ``--seconds`` and the drain, and
prints one JSON line: the window's end-to-end numbers and the mean
waiting-queue depth over the first and the last third of the window.
The knee is the highest rate the system sustains: every request due in
the window finished within the drain cap, and the last third queues no
deeper than the first.  The sweep stops after the first rate at which a
due request is still open at the cap.  The cell runs at 0.8x the knee.
Not part of a benchmark run; it needs the chip like one.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[1] / "src"))


def queue_thirds(gauges, w0: float, w1: float):
    """Mean queue depth over the first and last third of ``[w0, w1)``."""
    third = (w1 - w0) / 3
    first = [g.queue_depth for g in gauges if w0 <= g.t < w0 + third]
    last = [g.queue_depth for g in gauges if w1 - third <= g.t < w1]

    def mean(xs):
        return sum(xs) / len(xs) if xs else 0.0
    return mean(first), mean(last)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    rates = sorted(float(r) for r in args.rates.split(","))

    from onchip import harness, traffic
    cell = harness.load_cell(args.workload)
    dev = harness.require_chips(cell.chips)[0]
    harness.setup_compile_cache(cell.root)
    spec, mix = cell.spec, cell.mix
    plans = {r: traffic.generate(mix, args.seed, args.seconds, spec.vocab,
                                 max_total=spec.max_seq_len, rate=r)
             for r in rates}
    srv = harness.setup(cell, args.seed,
                        max(q.input_len for p in plans.values() for q in p))
    stats = dev.memory_stats() or {}
    print(f"setup {time.perf_counter() - T_PROC0:.1f} s; "
          f"memory after set-up: {stats.get('bytes_in_use')} in use, "
          f"peak {stats.get('peak_bytes_in_use')}, "
          f"limit {stats.get('bytes_limit')}", flush=True)
    for r in rates:
        reqs = plans[r]
        off = harness.offer(srv, reqs, mix.ramp_s, args.seconds,
                            mix.drain_cap_s)
        m = traffic.window_metrics(reqs, harness.outcomes(off), mix.ramp_s,
                                   args.seconds, off.t_end)
        w0 = off.t_base + mix.ramp_s
        first, last = queue_thirds(srv.loop.metrics.gauges, w0,
                                   w0 + args.seconds)
        g = [x for x in srv.loop.metrics.gauges
             if w0 <= x.t < w0 + args.seconds]
        m.update({"rate": r, "queue_first_third": first,
                  "queue_last_third": last,
                  "occupancy": sum(x.active for x in g) / max(len(g), 1),
                  "min_free_blocks": min((x.free_blocks for x in g),
                                         default=None),
                  "drain_s": off.t_end - mix.ramp_s - args.seconds,
                  "peak_bytes": (dev.memory_stats() or {})
                  .get("peak_bytes_in_use")})
        print(json.dumps(m), flush=True)
        if m["failed"] > 0:
            print("stopping: past the knee (a due request was still "
                  "open at the drain cap)")
            break
        srv.loop.serve()            # finish what the cap left, then go on
    return 0


if __name__ == "__main__":
    sys.exit(main())
