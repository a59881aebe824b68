"""Each per-layer reader's arithmetic on a hand-made record (CPU)."""
import dataclasses
import importlib.util

import pytest

from conftest import ROOT
from onchip import harness, work
from onchip.model import load_config
from onchip.trace_reduce import Event, Trace

METRICS = ROOT / "benchmarks" / "onchip" / "metrics"
CONFIG, ARCH = load_config(ROOT / "benchmarks" / "onchip" / "configs"
                           / "qwen3-1.7b.json")
SPEC = ARCH.load_spec(CONFIG)
PEAK = work.peaks("TPU v5 lite")
DEV = "/device:TPU:0"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Gauge:
    active: int
    dispatch_width: int


def record(**kw):
    rec = harness.Record(arch=ARCH, spec=SPEC, peak=PEAK)
    for k, v in kw.items():
        setattr(rec, k, v)
    return rec


def test_occupancy_and_pad_share():
    gauges = [Gauge(3, 4), Gauge(5, 8), Gauge(0, 0)]
    rec = record(gauges=gauges)
    assert reader("loop.occupancy")(rec) == pytest.approx(8 / 3)
    assert reader("engine.decode_pad_share")(rec) \
        == pytest.approx(100 * (1 - 8 / 12))
    assert reader("loop.occupancy")(record()) is None


def test_decide_ms():
    rec = record(decide_s=[0.001, 0.003])
    assert reader("sched.decide_ms")(rec) == pytest.approx(2.0)
    assert reader("sched.decide_ms")(record()) is None


def _traced(ops, modules, **kw):
    return record(trace=Trace(ops={DEV: ops}, modules={DEV: modules},
                              spans=[]),
                  window_ns=(0, 1_000_000_000), device=DEV, **kw)


def test_device_readers_against_work_functions():
    ctx = [[100, 300], [101, 301]]
    decode_ns, prefill_ns, kernel_ns = 40_000_000, 10_000_000, 2_000_000
    rec = _traced(
        ops=[Event("decode_attention_paged", 0, kernel_ns),
             Event("fusion", 500_000_000, 600_000_000)],
        modules=[Event("jit__decode_dispatch_paged(1)", 0, decode_ns),
                 Event("jit__prefill_paged(2)", 700_000_000,
                       700_000_000 + prefill_ns)],
        decode_ctx=ctx, prefill_n=[500])
    least = sum(work.roofline_s(*ARCH.decode_step(SPEC, c), PEAK)
                for c in ctx)
    assert reader("mfu.decode")(rec) == pytest.approx(
        100 * least / (decode_ns / 1e9))
    assert reader("mfu.prefill")(rec) == pytest.approx(
        100 * work.roofline_s(*ARCH.prefill(SPEC, 500), PEAK)
        / (prefill_ns / 1e9))
    k_least = SPEC.layers * sum(
        work.roofline_s(*ARCH.paged_attention(SPEC, c), PEAK) for c in ctx)
    assert reader("decode_attention_paged_roofline")(rec) == pytest.approx(
        100 * k_least / (kernel_ns / 1e9))
    # busy: 2 ms of kernel and 100 ms of fusion in a 1 s window
    assert reader("device.idle_share")(rec) == pytest.approx(100 * 0.898)


@pytest.mark.parametrize("name", ["mfu.decode", "mfu.prefill",
                                  "decode_attention_paged_roofline",
                                  "device.idle_share"])
def test_device_readers_leave_out_what_they_cannot_read(name):
    """No trace, or no matching events, gives no number (never 0)."""
    assert reader(name)(record()) is None
    assert reader(name)(_traced([], [], decode_ctx=[[5]],
                                prefill_n=[5])) is None
