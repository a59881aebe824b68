"""The program-span reduction, the device scopes and every per-layer
reader, the new ones included, on a trace recorded on a TPU v5e with the
program's own spans (``record_spans_trace.py``: the two-layer decoder of
``record_trace.py`` served through the harness, its traced window kept
with the optimized HLO of the step programs that ran, the run's step
records and the numbers it printed).  The reduction here, on the CPU,
has to give the same numbers from the same files."""
import gzip
import json
import types
from pathlib import Path

import pytest

from onchip import breakdown, harness, trace_reduce, work
from onchip.model import load_arch

DATA = Path(__file__).resolve().parent / "data"
DENSE = load_arch("dense")        # the recorded config's architecture
RUN = json.loads((DATA / "tiny_v5e_spans.json").read_text())


@pytest.fixture
def pb(tmp_path):
    (tmp_path / "p").mkdir()
    with gzip.open(DATA / "tiny_v5e_spans.xplane.pb.gz", "rb") as f:
        (tmp_path / "p" / "w.xplane.pb").write_bytes(f.read())
    return tmp_path / "p" / "w.xplane.pb"


def _pairs_match(got, want):
    assert [n for n, _ in got] == [n for n, _ in want]
    assert [v for _, v in got] == pytest.approx([v for _, v in want],
                                                rel=1e-9)


def test_program_spans_and_scopes_match_the_chip_run(pb):
    with gzip.open(DATA / "tiny_v5e_spans.hlo.json.gz", "rt") as f:
        texts = json.load(f)
    got, want = breakdown.reduce(str(pb), texts), RUN["program"]
    assert got["program_spans"] == want["program_spans"]
    for key in ("idle_gaps", "device_scopes", "unscoped"):
        _pairs_match(got[key], want[key])
    # every span of the tick is there, and no idle gap inside a tick is
    # left to the harness's own span
    assert {"serve.tick", "serve.ingest", "serve.schedule",
            "engine.dispatch", "serve.consume", "serve.sync",
            "engine.prefill", "engine.sync"} <= set(got["program_spans"])
    assert all(n != "bench.tick" for n, _ in got["idle_gaps"])
    # the step programs' own scopes hold device time
    assert {"mlp", "kv_write"} <= {n for n, _ in got["device_scopes"]}


def test_every_per_layer_number_matches_the_chip_run(pb):
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
    device = {}
    got = harness._per_layer(cell, rec, str(pb.parent), device, {},
                             log=lambda s: None)
    assert {"sched.queue_wait_ms", "loop.host_ms"} <= set(got)
    assert set(got) == set(RUN["metrics"])
    for name, m in RUN["metrics"].items():
        assert got[name]["value"] == pytest.approx(m["value"], rel=1e-9)
    assert device["busy_s"] == pytest.approx(RUN["device"]["busy_s"])


def test_the_harness_reduction_reads_only_its_own_spans(pb):
    """The benchmark's own loader still sees the ``bench.*`` spans alone
    beside the program's, so the accepted readers read what they read."""
    tr = trace_reduce.load(str(pb))
    assert {s.name for s in tr.spans} <= {"bench.window", "bench.tick",
                                          "bench.decide", "bench.prefill",
                                          "bench.dispatch"}
    assert "decode_attention_paged" in {e.name for e in
                                        tr.ops["/device:TPU:0"]}
