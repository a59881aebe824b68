"""Shared pieces of the on-chip benchmark's CPU tests: the ``onchip``
package on the import path, a tiny configuration and mix, and a stand-in
for the chip's device object."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks"))

TINY_CONFIG = {
    "name": "tiny", "arch": "dense", "source": "a CPU-sized dense decoder",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "reduced": [],
    "program": {"family": "dense", "norm_type": "rmsnorm",
                "mlp_type": "swiglu", "qk_norm": True, "dtype": "bfloat16"},
    "serving": {"max_slots": 4, "block_size": 16, "max_seq_len": 128,
                "num_blocks": 64},
    # bf16 on the CPU at this size reads widest gaps of 0.015-0.028 and
    # the fp8 control 0.40-0.49 (seeds 11-13); the check covers every
    # finished request
    "check": {"logit_gap_limit": 0.1, "min_tokens": 100000,
              "max_requests": 1000},
}

TINY_MIX = {
    "profiles": ["python-code-23k-sharegpt", "sharegpt-vicuna"],
    "mix": [0.5, 0.5], "base_seed": 3,
    "arrival": {"process": "poisson", "rate": 20.0},
    "ramp_s": 1, "drain_cap_s": 60, "slo_scale": 1.0,
    "policy": "slo-reanneal", "discipline": "stall",
}


class FakeChip:
    """What the harness reads of a device, for a CPU run that stands in
    for the chip: its kind (for the peaks table), platform and memory."""
    platform = "cpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


@pytest.fixture
def bench_root(tmp_path):
    """A copy of the benchmark's files (BENCHMARK.json, configs,
    architecture modules, mixes, traces, readers) under ``tmp_path``, to
    which a test adds."""
    src = ROOT / "benchmarks" / "onchip"
    dst = tmp_path / "benchmarks" / "onchip"
    for sub in ("configs", "archs", "traffic", "traces", "metrics"):
        shutil.copytree(src / sub, dst / sub)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def add_cell(root: Path, cell: str, config: dict, mix: dict, metric=None):
    """Add a configuration, a mix and optionally a per-layer metric as new
    files plus new entries in BENCHMARK.json, as a later change would."""
    d = root / "benchmarks" / "onchip"
    (d / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    (d / "traffic" / f"{cell}.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    name = f"{config['name']}.{cell}"
    bench["workloads"].append({"name": name, "config": config["name"],
                               "traffic": cell, "chips": 1, "why": "test"})
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(name)
    if metric is not None:
        mname, src = metric
        (d / "metrics" / f"{mname}.py").write_text(src)
        bench["per_layer"].append({
            "name": mname, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "serving loop",
            "moves": "output_tok_s", "workloads": [name]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name
