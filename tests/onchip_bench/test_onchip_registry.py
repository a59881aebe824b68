"""A cell, a configuration, an architecture module, a mix and a per-layer
metric added only as new files are found by name and run end to end, on
the CPU at a tiny size.

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


STUB_ARCH = '''"""A stand-in architecture: the dense module's functions,
each call counted."""
from collections import Counter
from pathlib import Path

from onchip import model

_dense = model.load_arch("dense", Path(__file__).parent)
CALLS = Counter()


def _counted(name):
    fn = getattr(_dense, name)

    def call(*a, **k):
        CALLS[name] += 1
        return fn(*a, **k)
    return call


load_spec = _counted("load_spec")
program_config = _counted("program_config")
make_params = _counted("make_params")
Reference = _counted("Reference")
decode_step = _counted("decode_step")
prefill = _counted("prefill")
paged_attention = _counted("paged_attention")
'''


def _step_trace(rec):
    """One run each of the decode and the prefill program in the window
    (the CPU trace has no TPU plane), so the device readers count work."""
    from onchip import trace_reduce as tr
    dev = "/device:TPU:0"
    rec.trace = tr.Trace(ops={dev: []}, modules={dev: [
        tr.Event("jit__decode_dispatch_paged(1)", 0, 10**6),
        tr.Event("jit__prefill_paged(2)", 2 * 10**6, 3 * 10**6)]},
        spans=[])
    rec.device, rec.window_ns = dev, (0, 10**9)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_new_architecture_runs_as_new_files(bench_root, monkeypatch,
                                              trace):
    """An ``archs/`` module, a config that names it and a workload entry
    run end to end, and the harness and the readers reach the spec, the
    weights, the reference and the work counts through that module."""
    from onchip import harness
    (bench_root / "benchmarks" / "onchip" / "archs" / "stub.py") \
        .write_text(STUB_ARCH)
    config = dict(TINY_CONFIG, name="stubtiny", arch="stub")
    name = add_cell(bench_root, "tinymix", config, TINY_MIX)
    kept = {}
    per_layer = harness._per_layer

    def keep(cell_, rec, *a, **k):
        kept["rec"] = rec
        return per_layer(cell_, rec, *a, **k)
    monkeypatch.setattr(harness, "_per_layer", keep)
    cell, out = _run(bench_root, name, bool(trace))
    calls = cell.arch.CALLS
    assert cell.arch.__file__.endswith("stub.py")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {k: calls[k] for k in ("load_spec", "program_config",
                                  "make_params", "Reference")} \
        == dict(load_spec=1, program_config=1, make_params=1, Reference=1)
    if not trace:
        assert calls["decode_step"] == calls["prefill"] == 0
        return
    rec = kept["rec"]
    assert rec.arch is cell.arch and rec.decode_ctx and rec.prefill_n
    _step_trace(rec)
    readers = harness._load_readers(cell)
    for m in ("mfu.decode", "mfu.prefill"):
        assert readers[m].read(rec) > 0
    assert calls["decode_step"] == sum(1 for c in rec.decode_ctx if c)
    assert calls["prefill"] == sum(1 for n in rec.prefill_n if n > 0)


def test_a_config_without_an_architecture_is_refused(bench_root):
    from onchip import harness
    config = {k: v for k, v in TINY_CONFIG.items() if k != "arch"}
    name = add_cell(bench_root, "tinymix", config, TINY_MIX)
    with pytest.raises(SystemExit, match="names no architecture.*dense"):
        harness.load_cell(name, bench_root)


def test_an_unknown_architecture_is_refused(bench_root):
    from onchip import harness
    config = dict(TINY_CONFIG, arch="no-such-arch")
    name = add_cell(bench_root, "tinymix", config, TINY_MIX)
    with pytest.raises(SystemExit, match="no-such-arch.*dense"):
        harness.load_cell(name, bench_root)


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
