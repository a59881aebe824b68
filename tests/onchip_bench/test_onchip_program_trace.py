"""The reduction of the program's own spans and the device's named scopes
(``program_trace``), and the readers of the per-layer metrics that the
loop's counters feed, on hand-made records and a CPU trace."""
import dataclasses
import glob
import importlib.util
import tempfile

import jax
import numpy as np
import pytest

from conftest import ROOT
from onchip import program_trace as pt
from onchip import trace_reduce
from onchip.trace_reduce import Event

METRICS = ROOT / "benchmarks" / "onchip" / "metrics"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Gauge:
    dispatch_width: int
    t: float = 10.0
    tick_s: float = 0.0
    sync_s: float = 0.0
    queue_waits: tuple = ()


@dataclasses.dataclass
class OldGauge:
    """A gauge of a loop that has no such counters."""
    active: int
    dispatch_width: int
    t: float = 10.0


@dataclasses.dataclass
class Rec:
    gauges: list


def test_queue_wait_is_the_p95_of_every_first_admission():
    waits = [0.001 * i for i in range(1, 21)]
    rec = Rec([Gauge(4, t=10.0),
               Gauge(0, t=10.1, queue_waits=tuple(waits[:7])),
               Gauge(2, t=10.2, queue_waits=tuple(waits[7:]))])
    assert reader("sched.queue_wait_ms")(rec) == pytest.approx(
        1e3 * np.percentile(waits, 95))
    assert reader("sched.queue_wait_ms")(Rec([Gauge(4)])) is None
    assert reader("sched.queue_wait_ms")(Rec([OldGauge(1, 4)])) is None
    assert reader("sched.queue_wait_ms")(Rec([])) is None


def test_queue_wait_leaves_out_requests_due_before_the_window():
    """Due time is the admitting tick's start less the wait: a request
    of the ramp admitted in the window does not count."""
    rec = Rec([Gauge(4, t=10.0, queue_waits=(0.5, 0.0)),   # due 9.5, 10.0
               Gauge(4, t=10.3, queue_waits=(0.2, 0.31)),  # due 10.1, 9.99
               Gauge(4, t=10.4, queue_waits=(0.05,))])     # due 10.35
    assert reader("sched.queue_wait_ms")(rec) == pytest.approx(
        1e3 * np.percentile([0.0, 0.2, 0.05], 95))
    assert reader("sched.queue_wait_ms")(
        Rec([Gauge(4, t=10.0), Gauge(4, t=10.2, queue_waits=(0.3,))])) is None


def test_host_ms_is_tick_less_sync_over_dispatching_ticks():
    rec = Rec([Gauge(4, tick_s=0.010, sync_s=0.007),
               Gauge(8, tick_s=0.020, sync_s=0.015),
               Gauge(0, tick_s=0.500, sync_s=0.0)])    # no round: left out
    assert reader("loop.host_ms")(rec) == pytest.approx(4.0)
    assert reader("loop.host_ms")(Rec([Gauge(0, tick_s=1.0)])) is None
    assert reader("loop.host_ms")(Rec([OldGauge(1, 4)])) is None
    assert reader("loop.host_ms")(Rec([])) is None


@pytest.mark.parametrize("raw, name, args", [
    ("serve.tick", "serve.tick", {}),
    ("engine.prefill#req=3,tokens=120#", "engine.prefill",
     {"req": 3, "tokens": 120}),
    ("engine.warm#program=prefill,length=64#", "engine.warm",
     {"program": "prefill", "length": 64}),
])
def test_span_names_are_bare_and_args_apart(raw, name, args):
    assert pt.parse_name(raw) == (name, args)


def _sp(name, s, e):
    return pt.Span(name, s, e)


def test_idle_gaps_are_named_by_the_innermost_program_span():
    spans = [_sp("bench.tick", 0, 100), _sp("serve.tick", 1, 99),
             _sp("serve.consume", 40, 90), _sp("serve.sync", 41, 80)]
    gap = trace_reduce.gaps(trace_reduce.busy(
        [Event("fusion", 0, 45), Event("copy", 75, 100)], 0, 100), 0, 100)
    assert trace_reduce.labelled_gaps(gap, spans) == [("serve.sync", 30)]


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_decode_dispatch_paged)/jit(main)/attn_proj/dot_general",
     "attn_proj"),
    ("jit(_decode_dispatch_paged)/attend/jit(decode_attention_paged)/"
     "decode_attention_paged/pallas_call", "attend/decode_attention_paged"),
    ("jit(_decode_dispatch_paged)/scatter", "<unscoped>"),
    ("cache['layers'][0]['k']", "<unscoped>"),
    (None, "<unscoped>"),
])
def test_scope_of_an_op_name(op_name, scope):
    assert pt.scope_of(op_name) == scope


HLO = """HloModule jit__decode_dispatch_paged, is_scheduled=true

ENTRY %main.1 (p: bf16[8,128]) -> bf16[8,128] {
  %copy.7 = bf16[2,600,256,128]{3,0,2,1:T(2,128)(2,1)} copy(%p.1), metadata={op_name="cache['layers'][0]['k']"}
  %fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(_decode_dispatch_paged)/mlp/dot_general" stack_frame_id=3}
  ROOT %copy.9 = bf16[8,128]{1,0} copy(%fusion.3)
}
"""


def test_device_time_by_scope_from_the_optimized_hlo():
    index = pt.scope_index([HLO])
    ops = [
        Event("%copy.7 = bf16[2,600,256,128]{3,0,2,1:T(2,128)(2,1)} "
              "copy(bf16[2,600,256,128]{3,2,1,0} %p.1)", 10, 40),
        Event("%fusion.3 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%p), "
              "kind=kLoop", 40, 50),
        Event("%copy.9 = bf16[8,128]{1,0} copy(%fusion.3)", 50, 52),
        Event("%fusion.1 = f32[2]{0} fusion(%x)", 105, 110),
    ]
    modules = [Event("jit__decode_dispatch_paged(77)", 0, 60),
               Event("jit__threefry_split(5)", 100, 120)]
    scopes, unscoped = pt.device_scopes(ops, modules, index, 0, 1000)
    assert scopes == {"<unscoped>": 32, "mlp": 10,
                      "<jit__threefry_split>": 5}
    assert unscoped == {"copy bf16[2,600,256,128]": 30, "copy bf16[8,128]": 2}


def test_a_served_cpu_trace_loads_with_bare_names_and_parsed_args():
    from repro.engine.engine import Engine
    from repro.models import ModelConfig, init_params
    from repro.serving import ServeLoop
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=2, d_ff=64, vocab_size=64,
                      dtype="float32")
    loop = ServeLoop(Engine(cfg, init_params(jax.random.PRNGKey(0), cfg),
                            max_slots=2, max_seq_len=64), "fcfs")
    loop.start(warm_lengths=[8])
    ids = [loop.submit(np.arange(n, dtype=np.int32),
                       max_new_tokens=3).req_id for n in (8, 12)]
    d = tempfile.mkdtemp()
    with jax.profiler.trace(d):
        loop.serve()
    spans = pt.load_spans(glob.glob(f"{d}/**/*.xplane.pb",
                                    recursive=True)[0])
    names = {s.name for s in spans}
    assert {"serve.tick", "serve.consume", "serve.sync", "engine.prefill",
            "engine.dispatch", "sched.decide"} <= names
    assert all("#" not in n for n in names)
    assert sorted((s.args["req"], s.args["tokens"]) for s in spans
                  if s.name == "engine.prefill") == [(ids[0], 8), (ids[1], 12)]
    assert [s.start for s in spans] == sorted(s.start for s in spans)
