#!/usr/bin/env python3
"""Record the small chip trace that ``test_onchip_trace_reduce.py`` reads.

    python3 tests/onchip_bench/record_trace.py --out <dir>

Runs a traced benchmark run of a two-layer decoder (head size 128, so the
paged Pallas kernel compiles for the chip) through the harness, on the
TPU, and keeps what the run reduced its numbers from:
``tiny_v5e.xplane.pb.gz`` (the profiler's trace of the window) and
``tiny_v5e.json`` (the run's step records, its per-layer numbers and its
``device`` object).  Copy both beside the test.  Not part of a benchmark
run or of the tests.
"""
import argparse
import dataclasses
import glob
import gzip
import json
import os
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

TRACE_CONFIG = {
    "name": "tracetiny", "arch": "dense",
    "source": "a two-layer decoder at the chip's tile",
    "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
    "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 128,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "reduced": [],
    "program": {"family": "dense", "norm_type": "rmsnorm",
                "mlp_type": "swiglu", "qk_norm": True, "dtype": "bfloat16"},
    "serving": {"max_slots": 4, "block_size": 16, "max_seq_len": 256,
                "num_blocks": 64},
    "check": {"logit_gap_limit": 0.1, "min_tokens": 100000,
              "max_requests": 1000},
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    from conftest import TINY_MIX, add_cell
    from onchip import harness
    root = Path(tempfile.mkdtemp(prefix="record_trace_"))
    src = ROOT / "benchmarks" / "onchip"
    for sub in ("configs", "archs", "traffic", "traces", "metrics"):
        shutil.copytree(src / sub, root / "benchmarks" / "onchip" / sub)
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    mix = dict(TINY_MIX, arrival={"process": "poisson", "rate": 8.0})
    name = add_cell(root, "tinymix", TRACE_CONFIG, mix)
    cell = harness.load_cell(name, root)
    devices = harness.require_chips(1)
    harness.setup_compile_cache(ROOT)

    # keep the profile directory and the step records the run reduces
    prof = root / "prof"
    kept = {}
    per_layer = harness._per_layer

    def keep(cell_, rec, prof_dir, device, out_, log):
        pb = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                       recursive=True)[0]
        with open(pb, "rb") as f, \
                gzip.open(out / "tiny_v5e.xplane.pb.gz", "wb") as g:
            g.write(f.read())
        kept.update(
            gauges=[dataclasses.asdict(x) for x in rec.gauges],
            decide_s=rec.decide_s, decode_ctx=rec.decode_ctx,
            prefill_n=rec.prefill_n)
        return per_layer(cell_, rec, prof_dir, device, out_, log)
    harness._per_layer = keep
    harness.tempfile.mkdtemp = lambda prefix="": str(prof)
    res = harness.run(cell, 5, args.seconds, True, devices,
                      time.perf_counter(), log=print)
    kept.update(config=TRACE_CONFIG, metrics=res["metrics"],
                device=res["device"], breakdown=res["breakdown"])
    (out / "tiny_v5e.json").write_text(json.dumps(kept, indent=1))
    print(json.dumps({k: res[k] for k in ("metrics", "device")}))
    shutil.rmtree(root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
