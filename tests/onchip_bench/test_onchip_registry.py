"""A cell, a configuration, a mix and a per-layer metric added only as new
files are found by name and run end to end, on the CPU at a tiny size.

The test stands in for the chip: it passes the harness a device object
itself instead of the harness's own look for a TPU."""
import os
import subprocess
import sys

import jax
import pytest

from conftest import ROOT, TINY_CONFIG, TINY_MIX, FakeChip, add_cell

COUNT_READER = '''"""ticks.count (count): serving ticks in the traced window."""


def read(rec):
    return float(len(rec.gauges)) if rec.gauges else None
'''


def _run(root, name, trace, seed=2**33 + 5):
    from onchip import harness
    cell = harness.load_cell(name, root)
    return cell, harness.run(cell, seed, 2.0, trace, [FakeChip()],
                             t_proc0=0.0, log=lambda s: None)


@pytest.mark.parametrize("trace", [0, 1])
def test_new_files_are_found_by_name_and_run(bench_root, trace):
    name = add_cell(bench_root, "tinymix", TINY_CONFIG, TINY_MIX,
                    metric=("ticks.count", COUNT_READER))
    cell, out = _run(bench_root, name, bool(trace))
    assert cell.spec.d_model == 64 and cell.mix.name == "tinymix"
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out["checks"]) == ["unfinished", "logit_gap"]
    if trace:
        # the new reader ran; device readers find no TPU plane on the CPU
        # and leave their metrics out rather than report 0
        assert out["metrics"]["ticks.count"]["value"] > 0
        assert "loop.occupancy" in out["metrics"]
        assert "mfu.decode" not in out["metrics"]
        assert "breakdown" in out
    else:
        assert set(out["metrics"]) >= {"ttft_p95_ms", "tbt_p95_ms",
                                       "output_tok_s", "slo_attainment",
                                       "setup_s"}


def test_unknown_workload_is_refused(bench_root):
    from onchip import harness
    with pytest.raises(SystemExit):
        harness.load_cell("no-such.cell", bench_root)


def test_no_tpu_means_no_run(bench_root, capsys):
    """On this CPU-only host the harness's own look for a chip refuses."""
    from onchip import harness
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as e:
        harness.require_chips(1)
    assert e.value.code != 0
    assert capsys.readouterr().out == ""


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    """The command as the benchmark states it, on this CPU-only host."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/onchip/run.py", "--workload",
         "qwen3-1.7b.mixed-slo", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
