#!/usr/bin/env python3
"""Record the small chip trace with program spans that
``test_onchip_chip_spans.py`` reads.

    python3 tests/onchip_bench/record_spans_trace.py --out <dir>

The two-layer decoder of ``record_trace.py``, served through the harness
on the TPU in one traced run of ``breakdown.py``'s kind, and kept with
what the run reduced its numbers from: ``tiny_v5e_spans.xplane.pb.gz``
(the window's trace: device ops and the program's ``serve.*``,
``sched.*``, ``engine.*`` spans), ``tiny_v5e_spans.hlo.json.gz`` (the
optimized HLO of the step programs that ran) and
``tiny_v5e_spans.json`` (the run's step records and gauges, its
per-layer numbers, its ``device`` object and the program-span and scope
numbers it printed).  Copy the three files beside the test.  Not part of
a benchmark run or of the tests.
"""
import argparse
import dataclasses
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "benchmarks"))
sys.path.insert(0, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    from conftest import TINY_MIX, add_cell
    from onchip import breakdown, harness
    from record_trace import TRACE_CONFIG
    root = Path(tempfile.mkdtemp(prefix="record_spans_"))
    src = ROOT / "benchmarks" / "onchip"
    for sub in ("configs", "archs", "traffic", "traces", "metrics"):
        shutil.copytree(src / sub, root / "benchmarks" / "onchip" / sub)
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    mix = dict(TINY_MIX, arrival={"process": "poisson", "rate": 8.0})
    cell = harness.load_cell(add_cell(root, "tinymix", TRACE_CONFIG, mix),
                             root)
    devices = harness.require_chips(1)
    harness.setup_compile_cache(ROOT)

    kept = {}
    per_layer = harness._per_layer

    def keep(cell_, rec, prof_dir, device, out_, log):
        kept.update(
            gauges=[dataclasses.asdict(x) for x in rec.gauges],
            decide_s=rec.decide_s, decode_ctx=rec.decode_ctx,
            prefill_n=rec.prefill_n)
        return per_layer(cell_, rec, prof_dir, device, out_, log)
    harness._per_layer = keep
    res, nums = breakdown.traced_run(cell, 5, args.seconds, devices,
                                     time.perf_counter(), log=print,
                                     keep=root / "kept")
    shutil.copy(root / "kept" / "window.xplane.pb.gz",
                out / "tiny_v5e_spans.xplane.pb.gz")
    shutil.copy(root / "kept" / "hlo.json.gz",
                out / "tiny_v5e_spans.hlo.json.gz")
    kept.update(config=TRACE_CONFIG, metrics=res["metrics"],
                device=res["device"], breakdown=res["breakdown"],
                program=nums)
    (out / "tiny_v5e_spans.json").write_text(json.dumps(kept, indent=1))
    with gzip.open(out / "tiny_v5e_spans.hlo.json.gz", "rt") as f:
        print(f"kept {len(json.load(f))} HLO texts")
    print(json.dumps({k: res[k] for k in ("metrics", "device")}))
    shutil.rmtree(root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
