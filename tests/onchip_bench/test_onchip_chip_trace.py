"""The trace reduction and the per-layer readers on a trace recorded on a
TPU v5e (``record_trace.py``: a two-layer decoder served through the
harness, its traced window kept with the run's step records and the
numbers the run printed).  The reduction here, on the CPU, has to give
the same numbers from the same file."""
import gzip
import json
import types
from pathlib import Path

import pytest

from onchip import harness, trace_reduce, work
from onchip.model import load_arch

DATA = Path(__file__).resolve().parent / "data"
DENSE = load_arch("dense")        # the recorded config's architecture
RUN = json.loads((DATA / "tiny_v5e.json").read_text())


def unpack(d: Path) -> Path:
    d.mkdir()
    with gzip.open(DATA / "tiny_v5e.xplane.pb.gz", "rb") as f:
        (d / "tiny_v5e.xplane.pb").write_bytes(f.read())
    return d


def test_planes_lines_and_names_are_those_the_reduction_reads(tmp_path):
    pb = unpack(tmp_path / "p") / "tiny_v5e.xplane.pb"
    tr = trace_reduce.load(str(pb))
    assert tr.devices == ["/device:TPU:0"]
    ops = {e.name for e in tr.ops["/device:TPU:0"]}
    assert "decode_attention_paged" in ops
    mods = {trace_reduce.module_name(e.name)
            for e in tr.modules["/device:TPU:0"]}
    assert any("decode_dispatch_paged" in m for m in mods)
    spans = {s.name for s in tr.spans}
    assert {"bench.window", "bench.tick", "bench.dispatch"} <= spans


def test_per_layer_numbers_match_the_chip_run(tmp_path):
    rec = harness.Record(arch=DENSE, spec=DENSE.load_spec(RUN["config"]),
                         peak=work.peaks("TPU v5 lite"),
                         gauges=[types.SimpleNamespace(**g)
                                 for g in RUN["gauges"]],
                         decide_s=RUN["decide_s"],
                         decode_ctx=RUN["decode_ctx"],
                         prefill_n=RUN["prefill_n"])
    cell = types.SimpleNamespace(
        per_layer=[{"name": n, "unit": m["unit"]}
                   for n, m in RUN["metrics"].items()],
        bench_dir=harness.HERE)
    device, out = {}, {}
    got = harness._per_layer(cell, rec, str(unpack(tmp_path / "p")),
                             device, out,
                             log=lambda s: None)
    assert set(got) == set(RUN["metrics"])
    for name, m in RUN["metrics"].items():
        assert got[name]["value"] == pytest.approx(m["value"], rel=1e-9)
    assert device["busy_s"] == pytest.approx(RUN["device"]["busy_s"])
    assert device["window_s"] == pytest.approx(RUN["device"]["window_s"])
    assert 0 < device["busy_s"] <= device["window_s"]
    for name, m in got.items():
        if name.endswith("_roofline") or "mfu" in name:
            assert 0 < m["value"] <= 100, name
