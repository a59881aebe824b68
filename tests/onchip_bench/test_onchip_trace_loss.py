"""The per-layer reduction of a trace that lost its device events past some
point, as the profiler's device tracer drops them once its buffers are
full: the chip trace of ``test_onchip_chip_spans.py`` with every device
event that starts after a share of its window left out, read through
``harness._per_layer``.  The device-trace shares have to read as they do
on the whole trace, over the part of the window that the trace kept."""
import gzip
import json
import types
from pathlib import Path

import pytest

from onchip import harness, trace_reduce, work
from onchip.model import load_arch

DATA = Path(__file__).resolve().parent / "data"
DENSE = load_arch("dense")        # the recorded config's architecture
RUN = json.loads((DATA / "tiny_v5e_spans.json").read_text())
DEVICE_SHARES = ("mfu.decode", "mfu.prefill",
                 "decode_attention_paged_roofline", "device.idle_share")


def _cut(tr: trace_reduce.Trace, share: float) -> trace_reduce.Trace:
    win = next(s for s in tr.spans if s.name == "bench.window")
    t = win.start + share * win.dur

    def keep(evs):
        return [e for e in evs if e.start <= t]
    return trace_reduce.Trace(
        ops={d: keep(v) for d, v in tr.ops.items()},
        modules={d: keep(v) for d, v in tr.modules.items()},
        spans=tr.spans, dropped=tr.dropped)


def _read(tmp_path, monkeypatch, share=None, dropped=0):
    (tmp_path / "p").mkdir()
    with gzip.open(DATA / "tiny_v5e_spans.xplane.pb.gz", "rb") as f:
        (tmp_path / "p" / "w.xplane.pb").write_bytes(f.read())
    load = trace_reduce.load

    def cut(path):
        tr = load(path)
        tr.dropped = {d: dropped for d in tr.ops}
        return tr if share is None else _cut(tr, share)
    monkeypatch.setattr(trace_reduce, "load", cut)
    rec = harness.Record(arch=DENSE, spec=DENSE.load_spec(RUN["config"]),
                         peak=work.peaks("TPU v5 lite"),
                         gauges=[types.SimpleNamespace(**g)
                                 for g in RUN["gauges"]],
                         decide_s=list(RUN["decide_s"]),
                         decode_ctx=list(RUN["decode_ctx"]),
                         prefill_n=list(RUN["prefill_n"]))
    cell = types.SimpleNamespace(
        per_layer=[{"name": n, "unit": m["unit"]}
                   for n, m in RUN["metrics"].items()],
        bench_dir=harness.HERE)
    device, out, lines = {}, {}, []
    got = harness._per_layer(cell, rec, str(tmp_path / "p"), device, out,
                             log=lines.append)
    counts = json.loads(next(s for s in lines
                             if s.startswith("per-layer counts: "))
                        .split(": ", 1)[1])
    return got, device, out, counts


def test_a_whole_trace_keeps_its_window(tmp_path, monkeypatch):
    got, device, _, counts = _read(tmp_path, monkeypatch)
    assert device["traced_share"] == 1.0
    assert device["window_s"] == pytest.approx(RUN["device"]["window_s"])
    assert (counts["decode_steps"], counts["decode_modules"]) == (212, 212)
    assert (counts["prefills"], counts["prefill_modules"]) == (9, 9)
    for name in DEVICE_SHARES:
        assert got[name]["value"] == pytest.approx(
            RUN["metrics"][name]["value"], rel=1e-9)


def test_a_plane_that_reports_dropped_buffers_ends_at_its_last_event(
        tmp_path, monkeypatch):
    """Every step's program is in the trace, but the plane says it lost
    events: the window ends with its last device event (2.1 ms before the
    window closed) and keeps every step."""
    got, device, _, counts = _read(tmp_path, monkeypatch, dropped=153)
    assert 0.99 < device["traced_share"] < 1.0
    assert (counts["decode_steps"], counts["decode_modules"]) == (212, 212)
    assert (counts["prefills"], counts["prefill_modules"]) == (9, 9)
    for name in DEVICE_SHARES:
        assert got[name]["value"] == pytest.approx(
            RUN["metrics"][name]["value"], rel=0.01)


def test_a_trace_cut_at_35_percent_reads_as_the_whole(tmp_path, monkeypatch):
    got, device, out, counts = _read(tmp_path, monkeypatch, share=0.35)
    for name in DEVICE_SHARES:
        assert got[name]["value"] == pytest.approx(
            RUN["metrics"][name]["value"], rel=0.10), name
    assert device["traced_share"] == pytest.approx(0.35, abs=0.02)
    assert 0 < device["busy_s"] <= device["window_s"]
    # every step counted has its device program in the kept part
    assert counts["decode_modules"] == counts["decode_steps"] < 212
    assert counts["prefill_modules"] == counts["prefills"] < 9
    # the lost stretch is not shown as an idle gap
    assert max(s for _, s in out["breakdown"]["idle_gaps"]) < 0.05
    # host counters keep the whole window
    for name in ("loop.occupancy", "sched.decide_ms", "loop.host_ms"):
        assert got[name]["value"] == pytest.approx(
            RUN["metrics"][name]["value"], rel=1e-9)


def test_a_trace_whose_host_spans_miss_steps_is_refused(tmp_path,
                                                        monkeypatch):
    load = trace_reduce.load

    def fewer_spans(path):
        tr = load(path)
        first = next(s for s in tr.spans if s.name == "bench.dispatch")
        tr.spans = [s for s in tr.spans if s is not first]
        return tr
    monkeypatch.setattr(trace_reduce, "load", fewer_spans)
    with pytest.raises(RuntimeError, match="bench.dispatch"):
        _read(tmp_path, monkeypatch)
