"""The serving path's own spans and counters (CPU): every span of a tick
under ``jax.profiler.trace``, with its nesting and arguments; the
admission stamp; warm-path entries counted on the tick that paid them."""
import glob
import tempfile

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.latency_model import LinearLatencyModel
from repro.core.slo import SLO, Request
from repro.engine.engine import Engine
from repro.engine.request import Phase, RuntimeRequest
from repro.models import ModelConfig, init_params
from repro.serving import ServeLoop

CFG = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128,
                  dtype="float32")
SPANS = {"serve.tick", "serve.ingest", "serve.schedule", "sched.decide",
         "engine.prefill", "engine.dispatch", "engine.warm",
         "serve.consume", "serve.sync", "engine.sync"}


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _loop(params, policy="fcfs", model=None):
    eng = Engine(CFG, params, max_slots=2, max_seq_len=128, paged=True,
                 num_blocks=64)
    return ServeLoop(eng, policy, model=model)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(0, 128, n).astype(np.int32)


def _host_spans(path):
    """(name, start, end, args) of the program's spans, in start order."""
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.split("#")[0] in SPANS:
                    out.append((e.name.split("#")[0], e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _parent(spans, child):
    """The innermost span that holds ``child``."""
    _, s, e, _ = child
    holders = [p for p in spans if p is not child and p[1] <= s
               and e <= p[2] and (p[2] - p[1]) >= (e - s)]
    return min(holders, key=lambda p: p[2] - p[1], default=None)


def test_a_traced_tick_yields_every_span_nested_with_its_args(params):
    loop = _loop(params)
    loop.start(warm_lengths=[10])            # the 40-token bucket is not
    prompts = {loop.submit(_prompt(10, 1), max_new_tokens=4).req_id: 10,
               loop.submit(_prompt(40, 2), max_new_tokens=4).req_id: 40}
    d = tempfile.mkdtemp()
    with jax.profiler.trace(d):
        loop.serve()
    spans = _host_spans(glob.glob(f"{d}/**/*.xplane.pb",
                                  recursive=True)[0])
    assert {s[0] for s in spans} == SPANS
    parent = {"serve.ingest": "serve.tick", "serve.schedule": "serve.tick",
              "sched.decide": "serve.schedule",
              "engine.prefill": "serve.tick",
              "engine.dispatch": "serve.tick",
              "serve.consume": "serve.tick", "serve.sync": "serve.consume",
              "engine.sync": "engine.prefill",
              "engine.warm": "engine.prefill"}
    for s in spans:
        if s[0] != "serve.tick":
            assert _parent(spans, s)[0] == parent[s[0]], s
    prefills = {s[3]["req"]: s[3]["tokens"] for s in spans
                if s[0] == "engine.prefill"}
    assert prefills == prompts
    warm = [s[3] for s in spans if s[0] == "engine.warm"]
    assert warm == [{"program": "prefill", "length": 64}]
    assert {s[3]["width"] for s in spans if s[0] == "engine.dispatch"} \
        <= {1, 2}
    assert all(s[3]["waiting"] >= 1 for s in spans
               if s[0] == "sched.decide")
    for g in loop.metrics.gauges:
        assert 0 <= g.sync_s <= g.tick_s


def test_warms_count_an_unwarmed_prefill_bucket_on_its_tick(params):
    loop = _loop(params)
    loop.start(warm_lengths=[10])
    loop.submit(_prompt(10, 3), max_new_tokens=3)
    loop.submit(_prompt(40, 4), max_new_tokens=3, arrival_time=0.05)
    loop.serve()
    warmed = [g for g in loop.metrics.gauges if g.warms]
    assert len(warmed) == 1 and warmed[0].warms == 1
    assert warmed[0].prefill_tokens == 40
    assert loop.eng.warm_runs >= 1
    assert loop.metrics.summary()["warms"] == 1


def test_admit_lies_between_submit_and_first_token(params):
    model = LinearLatencyModel(alpha_p=1e-6, beta_p=1e-4, gamma_p=1e-5,
                               delta_p=2e-3, alpha_d=1e-7, beta_d=1e-4,
                               gamma_d=1e-6, delta_d=1e-3)
    loop = _loop(params, "slo-preempt", model)
    loop.start()
    for i in range(2):
        loop.submit(_prompt(30, 10 + i), max_new_tokens=24,
                    slo=SLO(e2e=60.0), task_type="code")
    for i in range(3):
        loop.submit(_prompt(12, 20 + i), max_new_tokens=3,
                    slo=SLO(ttft=0.03, tpot=0.05),
                    arrival_time=0.02 + i * 0.01)
    loop.serve()
    tls = list(loop.metrics.timelines.values())
    assert len(tls) == 5
    for tl in tls:
        assert tl.submit <= tl.admit <= tl.first_token
        assert tl.queue_wait == pytest.approx(tl.admit - tl.submit)
    # each request's wait is counted once, on the tick that first
    # admitted it, however often it was preempted and admitted again
    waits = sorted(w for g in loop.metrics.gauges for w in g.queue_waits)
    assert waits == pytest.approx(sorted(tl.queue_wait for tl in tls))
    s = loop.metrics.summary()
    assert s["queue_wait_p95"] == pytest.approx(
        np.percentile([tl.queue_wait for tl in tls], 95))


def test_admission_stamp_survives_a_preemption(params):
    eng = Engine(CFG, params, max_slots=1, max_seq_len=128, paged=True,
                 num_blocks=64)
    rt = RuntimeRequest(request=Request(req_id=0, task_type="chat",
                                        input_len=12, slo=SLO(),
                                        output_len=4),
                        prompt_tokens=_prompt(12, 5), max_new_tokens=4)
    eng.clock = 1.5
    assert eng._reserve_blocks(rt)
    eng.begin_prefill(rt, 0)
    eng.prefill_step(rt)
    assert rt.admit_time == 1.5 and rt.phase is Phase.RUNNING
    eng.preempt(rt)
    eng.clock = 7.0
    assert eng._reserve_blocks(rt)
    eng.begin_prefill(rt, 0)
    assert rt.admit_time == 1.5
