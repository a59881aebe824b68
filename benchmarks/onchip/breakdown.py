#!/usr/bin/env python3
"""One traced run of a cell, broken down by the program's own spans and
the device's named scopes.

    python3 benchmarks/onchip/breakdown.py --workload <name> --seed <n> \\
        --seconds <s> [--keep <dir>]

The run is the benchmark's ``--trace 1`` run (``harness.run``: the same
set-up, traffic, window, per-layer readers and check).  Before the
harness reduces the window's trace, this script reads the same trace
with :mod:`onchip.program_trace` and prints, before the result line:

* ``program spans:`` the count of each program span in the window;
* ``idle gaps by program span:`` the device's ten longest idle gaps,
  each named by the innermost span open in it (``serve.*``,
  ``engine.*``, ``sched.*`` inside the harness's ``bench.*``);
* ``device scopes:`` the ten scopes that held the most device time, and
  ``unscoped:`` the largest operations with no scope, by result shape.

The scopes come from the optimized HLO of the step programs that ran
(each decode width and prefill bucket of the window, compiled again
after the profiler stopped, from the persistent cache; not in
``setup_s``).  ``--keep`` writes the trace and those HLO texts there.
Not part of a benchmark run; it needs the chip like one.
"""
import time

T_PROC0 = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))                 # the onchip package
sys.path.insert(0, str(HERE.parents[1] / "src"))     # the system under test


def step_hlo_texts(eng, rec) -> list:
    """Optimized HLO of the decode widths and prefill buckets that ran in
    the window (``rec``: its gauges and prefill lengths)."""
    import jax
    import jax.numpy as jnp
    from repro.engine.engine import _bucket
    texts = []
    for w in sorted({g.dispatch_width for g in rec.gauges
                     if g.dispatch_width > 0}):
        texts.append(eng._dispatch_fn.lower(
            eng.params, eng.cache, jnp.zeros((w, 1), jnp.int32),
            jnp.zeros(w, bool), jax.random.PRNGKey(0)).compile().as_text())
    for L in sorted({_bucket(n) for n in rec.prefill_n if n > 0}):
        if L < eng.max_seq_len:
            texts.append(eng._prefill_fn.lower(
                eng.params, eng.cache, jnp.zeros((1, L), jnp.int32), L,
                0).compile().as_text())
    return texts


def reduce(pb: str, hlo_texts: list) -> dict:
    """The program-span and scope numbers of one traced window (``pb``:
    its ``.xplane.pb``), in seconds; nothing here needs a device."""
    from onchip import program_trace as pt
    from onchip import trace_reduce as tr
    spans = pt.load_spans(pb)
    win = next(s for s in spans if s.name == "bench.window")
    lo, hi = win.start, win.end
    ops, modules = pt.load_raw_ops(pb)
    dev = min(ops, default=None)
    counts = Counter(s.name for s in spans
                     if s.name.startswith(pt.PREFIXES)
                     and lo <= s.start < hi)
    gaps = tr.labelled_gaps(
        tr.gaps(tr.busy(ops.get(dev, []), lo, hi), lo, hi), spans)
    scopes, unscoped = pt.device_scopes(
        ops.get(dev, []), modules.get(dev, []), pt.scope_index(hlo_texts),
        lo, hi)

    def top(d, n=10):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:n]]
    return {"program_spans": dict(sorted(counts.items())),
            "idle_gaps": [[n, ns / 1e9] for n, ns in gaps],
            "device_scopes": top(scopes), "unscoped": top(unscoped, 5)}


def log_numbers(nums: dict, log):
    log("program spans: " + json.dumps(nums["program_spans"]))
    log("idle gaps by program span: " + json.dumps(nums["idle_gaps"]))
    log("device scopes: " + json.dumps(nums["device_scopes"]))
    log("unscoped: " + json.dumps(nums["unscoped"]))


def traced_run(cell, seed, seconds, devices, t_proc0, log, keep=None):
    """``harness.run(trace=True)`` with the program-span reduction made
    on its trace; returns (result, numbers)."""
    import glob
    import os
    from onchip import harness
    held, nums = {}, {}
    setup, per_layer = harness.setup, harness._per_layer

    def setup_kept(*a, **k):
        held["srv"] = setup(*a, **k)
        return held["srv"]

    def per_layer_too(cell_, rec, prof_dir, device, out, log_):
        pb = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                       recursive=True)[0]
        texts = step_hlo_texts(held.pop("srv").eng, rec)
        nums.update(reduce(pb, texts))
        log_numbers(nums, log_)
        if keep is not None:
            keep.mkdir(parents=True, exist_ok=True)
            with open(pb, "rb") as f, \
                    gzip.open(keep / "window.xplane.pb.gz", "wb") as g:
                g.write(f.read())
            with gzip.open(keep / "hlo.json.gz", "wt") as g:
                json.dump(texts, g)
        return per_layer(cell_, rec, prof_dir, device, out, log_)
    harness.setup, harness._per_layer = setup_kept, per_layer_too
    try:
        out = harness.run(cell, seed, seconds, True, devices, t_proc0,
                          log=log)
    finally:
        harness.setup, harness._per_layer = setup, per_layer
    return out, nums


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", type=Path, default=None)
    args = ap.parse_args()

    from onchip import harness
    cell = harness.load_cell(args.workload)
    devices = harness.require_chips(cell.chips)
    cache = harness.setup_compile_cache(cell.root)
    print(f"workload={cell.name} seed={args.seed} seconds={args.seconds:g} "
          f"trace=1 breakdown=1 device_kind={devices[0].device_kind} "
          f"compile_cache={cache}", flush=True)
    out, _ = traced_run(cell, args.seed, args.seconds, devices, T_PROC0,
                        log=lambda s: print(s, flush=True), keep=args.keep)
    checks = out.pop("checks")
    out["checks"] = checks                           # last key
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
