"""Streaming serving loop: overlap/sync token parity, measured-vs-engine
metric agreement, wall-clock arrival pacing, pow-2 dispatch bucketing,
preemption, rejection, and summary sanity."""
import time

import jax
import numpy as np
import pytest

from repro.core.latency_model import LinearLatencyModel
from repro.core.slo import SLO, Request
from repro.data.synthetic import sample_serve_workload
from repro.engine.engine import Engine
from repro.models import ModelConfig, init_params
from repro.serving import (ServeLoop, ServingMetrics, TokenStream,
                           UnsupportedDisciplineError)

CFG = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=128,
                  dtype="float32")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _engine(params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 128)
    return Engine(CFG, params, **kw)


def _prompts(n, seed=0, lo=8, hi=40):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, int(rng.integers(lo, hi))).astype(np.int32)
            for _ in range(n)]


def _run(params, overlap, paged, prompts, max_new=5, rate_gap=0.002,
         policy="fcfs", **loop_kw):
    eng = _engine(params, paged=paged,
                  num_blocks=64 if paged else None)
    loop = ServeLoop(eng, policy, overlap=overlap, **loop_kw)
    loop.start(warm_lengths=[len(p) for p in prompts])
    streams = [loop.submit(p, max_new_tokens=max_new,
                           slo=SLO(ttft=100.0, tpot=10.0),
                           arrival_time=i * rate_gap)
               for i, p in enumerate(prompts)]
    res = loop.serve()
    return loop, streams, res


@pytest.mark.parametrize("paged", [False, True])
def test_overlap_sync_token_parity(params, paged):
    """Greedy decode through the overlapped one-step-lookahead loop must
    produce exactly the tokens of the synchronous reference loop."""
    prompts = _prompts(7, seed=3)
    _, s_sync, _ = _run(params, overlap=False, paged=paged, prompts=prompts)
    _, s_over, _ = _run(params, overlap=True, paged=paged, prompts=prompts)
    for a, b in zip(s_sync, s_over):
        assert a.tokens == b.tokens
        assert len(a.tokens) == 5


def test_stream_metrics_agree_with_engine_accounting(params):
    """Measured TTFT/e2e from the token streams' wall-clock stamps must
    agree with the engine's own per-request accounting (the loop syncs
    the engine clock to the wall each tick; prefill advances it by the
    measured step time, so the two differ by at most one tick)."""
    loop, streams, res = _run(params, overlap=True, paged=True,
                              prompts=_prompts(6, seed=4))
    for st in streams:
        eng_row = res[st.req_id]
        assert st.ttft() == pytest.approx(eng_row["ttft"], abs=0.05)
        assert st.e2e() == pytest.approx(eng_row["e2e"], abs=0.05)
        assert st.tokens == eng_row["tokens"]
        # tbt gaps sum to the decode span of the measured e2e
        assert sum(st.tbts()) == pytest.approx(st.e2e() - st.ttft(),
                                               abs=1e-9)


def test_arrival_pacing_on_wall_clock(params):
    """A future arrival must not be admitted before its instant passes
    on the wall clock, and waiting counts from the arrival instant."""
    eng = _engine(params)
    loop = ServeLoop(eng, "fcfs")
    loop.start(warm_lengths=[16])
    prompts = _prompts(2, seed=5, lo=16, hi=17)
    t_arr = 0.15
    early = loop.submit(prompts[0], max_new_tokens=3, arrival_time=0.0)
    late = loop.submit(prompts[1], max_new_tokens=3, arrival_time=t_arr)
    loop.serve()
    assert early.events[0].t < t_arr
    assert late.submit_time == pytest.approx(t_arr, abs=0.06)
    assert late.events[0].t >= t_arr


def test_dispatch_widths_are_pow2_buckets(params):
    """Paged + bucketed dispatch must round batch width to powers of two
    covering the highest occupied slot, never the full pool when few
    slots are live."""
    loop, _, _ = _run(params, overlap=True, paged=True,
                      prompts=_prompts(3, seed=6), max_new=6,
                      rate_gap=0.0)
    widths = {g.dispatch_width for g in loop.metrics.gauges
              if g.dispatch_width > 0}
    assert widths, "no decode rounds dispatched"
    assert all(w & (w - 1) == 0 for w in widths)      # pow-2
    assert all(w <= 4 for w in widths)
    # 3 requests on 4 slots, lowest-slot-first: width never exceeds 4
    # and a single-request tail dispatches at width 1 or 2, not 4
    loop1, _, _ = _run(params, overlap=True, paged=True,
                       prompts=_prompts(1, seed=7), max_new=6)
    assert {g.dispatch_width for g in loop1.metrics.gauges
            if g.dispatch_width > 0} == {1}


def test_preemptive_policy_completes_all(params):
    """slo-preempt inside the serving loop: evictions re-queue the
    victim (KV recomputed on re-admission) and every request still
    finishes with its full token budget."""
    model = LinearLatencyModel(alpha_p=1e-6, beta_p=1e-4, gamma_p=1e-5,
                               delta_p=2e-3, alpha_d=1e-7, beta_d=1e-4,
                               gamma_d=1e-6, delta_d=1e-3)
    eng = _engine(params, max_slots=2, paged=True, num_blocks=64)
    loop = ServeLoop(eng, "slo-preempt", model=model)
    loop.start()
    streams = []
    # long loose-deadline jobs first, tight interactive arrivals behind
    for i, p in enumerate(_prompts(2, seed=8, lo=24, hi=40)):
        streams.append(loop.submit(p, max_new_tokens=24, slo=SLO(e2e=60.0),
                                   task_type="code", arrival_time=0.0))
    for i, p in enumerate(_prompts(3, seed=9, lo=8, hi=16)):
        streams.append(loop.submit(p, max_new_tokens=3,
                                   slo=SLO(ttft=0.03, tpot=0.05),
                                   arrival_time=0.02 + i * 0.01))
    res = loop.serve()
    assert len(res) == 5
    for st in streams:
        assert st.done and st.error is None
    budgets = [24, 24, 3, 3, 3]
    for st, want in zip(streams, budgets):
        assert len(st.tokens) == want


def test_unservable_request_is_rejected(params):
    """Prompts that cannot fit (length or lifetime KV footprint) fail
    their stream instead of wedging the loop."""
    eng = _engine(params, paged=True, num_blocks=8, block_size=16)
    loop = ServeLoop(eng, "fcfs")
    loop.start()
    ok = loop.submit(_prompts(1, seed=10, lo=16, hi=17)[0],
                     max_new_tokens=4)
    big = loop.submit(np.zeros(100, np.int32), max_new_tokens=60)
    loop.serve()
    assert ok.done and ok.error is None and len(ok.tokens) == 4
    assert big.error is not None and big.tokens == []
    s = loop.metrics.summary()
    assert s["rejected"] == 1 and s["n"] == 1


def test_summary_and_gauges_sanity(params):
    loop, streams, _ = _run(params, overlap=True, paged=True,
                            prompts=_prompts(6, seed=11))
    s = loop.metrics.summary()
    assert s["n"] == 6 and s["tokens"] == 30
    assert 0.0 <= s["attainment"] <= 1.0
    assert s["overlap_frac"] > 0.0          # lookahead actually engaged
    assert s["tokens_per_s"] > 0
    assert s["queue_depth_max"] >= 0
    rows = loop.metrics.rows()
    assert rows and rows[0][0] == "serve_summary"


def test_chunked_discipline_streams_end_to_end(params):
    """Chunked prefill streams natively (chunk-as-tick): every request
    completes with its full budget and the tokens equal the stalling
    run's — chunk boundaries change timing, not greedy content (each
    chunk attends exactly the same prefix KV)."""
    prompts = _prompts(5, seed=20, lo=20, hi=40)
    _, s_stall, _ = _run(params, overlap=True, paged=True, prompts=prompts)
    loop, s_chunk, res = _run(params, overlap=True, paged=True,
                              prompts=prompts, discipline="chunked:16")
    assert loop.disc.chunk_size == 16
    for a, b in zip(s_stall, s_chunk):
        assert b.done and b.error is None
        assert a.tokens == b.tokens and len(b.tokens) == 5
    assert len(res) == len(prompts)
    # at least one prompt spans several chunks: some tick carried
    # prefill work while slots were still mid-prefill afterwards
    gauges = loop.metrics.gauges
    assert sum(g.prefill_tokens for g in gauges) >= \
        sum(len(p) for p in prompts)
    assert any(g.prefilling > 0 for g in gauges)


def test_chunked_engine_default_adopted_and_dynamic_chunk_streams(params):
    """A chunk-configured engine streams under its own default, and
    dynamic-chunk (which carries AdaptiveChunkedPrefill) is executed —
    not refused — with every request completing."""
    from repro.core import PAPER_TABLE2
    eng = _engine(params, chunked_prefill=16)
    loop = ServeLoop(eng, "fcfs")
    assert loop.disc.chunk_size == 16
    eng2 = _engine(params, paged=True, num_blocks=64)
    loop2 = ServeLoop(eng2, "dynamic-chunk", model=PAPER_TABLE2)
    assert loop2.disc is loop2.pol.discipline    # identity: retune flows
    loop2.start()
    streams = [loop2.submit(p, max_new_tokens=4,
                            slo=SLO(ttft=100.0, tpot=10.0))
               for p in _prompts(4, seed=21, lo=20, hi=40)]
    loop2.serve()
    for st in streams:
        assert st.done and st.error is None and len(st.tokens) == 4


def test_chunked_on_mla_engine_raises_typed_error(params):
    """The one remaining unsupported combination: MLA archs have no
    chunked forward path, so a chunked discipline on an MLA engine is a
    configuration error — typed, catchable, at construction."""
    from repro.models.config import MLAConfig
    mla_cfg = ModelConfig(name="tiny-mla", family="dense", num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=4,
                          d_ff=128, vocab_size=128, dtype="float32",
                          mla=MLAConfig(kv_lora_rank=16, q_lora_rank=0,
                                        qk_nope_head_dim=8,
                                        qk_rope_head_dim=8, v_head_dim=8))
    mla_params = init_params(jax.random.PRNGKey(1), mla_cfg)
    eng = Engine(mla_cfg, mla_params, max_slots=2, max_seq_len=128)
    with pytest.raises(UnsupportedDisciplineError):
        ServeLoop(eng, "fcfs", discipline="chunked:16")
    # NotImplementedError subclassing keeps older handlers working
    with pytest.raises(NotImplementedError):
        ServeLoop(Engine(mla_cfg, mla_params, max_slots=2,
                         max_seq_len=128), "fcfs", discipline="chunked:16")


def test_stream_iteration_from_other_thread(params):
    """The blocking stream iterator drains tokens concurrently with the
    serving thread."""
    import threading
    eng = _engine(params)
    loop = ServeLoop(eng, "fcfs")
    loop.start(warm_lengths=[16])
    seen = []
    st = loop.submit(_prompts(1, seed=12, lo=16, hi=17)[0],
                     max_new_tokens=4)
    reader = threading.Thread(
        target=lambda: seen.extend(ev.token for ev in st))
    reader.start()
    loop.serve()
    reader.join(timeout=5)
    assert not reader.is_alive()
    assert seen == st.tokens and len(seen) == 4


def test_serve_workload_trace_replay(params):
    """sample_serve_workload pairs replay through submit_trace; measured
    wall attainment lands in the engine-style results."""
    pairs = sample_serve_workload(4, CFG.vocab_size, seed=13,
                                  arrival_rate=200.0, in_range=(8, 24),
                                  out_range=(3, 6))
    eng = _engine(params)
    loop = ServeLoop(eng, "fcfs")
    loop.start(warm_lengths=[len(p) for _, p in pairs])
    loop.submit_trace(pairs)
    res = loop.serve()
    assert len(res) == 4
    for v in res.values():
        assert "met_wall" in v and v["tokens"]


# ------------------------------------------------------------ launcher
def test_fit_latency_model_fits_or_refuses(params):
    """The launcher's fit uses this device's samples: enough warm-up
    requests give a fitted model, too few raise (no silent fallback to
    the paper's V100 coefficients)."""
    from repro.core.latency_model import PAPER_TABLE2
    from repro.launch.serve import fit_latency_model
    with pytest.raises(RuntimeError, match="more samples"):
        fit_latency_model(CFG, params, 2, np.random.default_rng(0),
                          n_warm=3)
    model = fit_latency_model(CFG, params, 2, np.random.default_rng(0))
    assert isinstance(model, LinearLatencyModel) and model != PAPER_TABLE2


@pytest.mark.parametrize("n", [1, 3])
def test_fleet_replicas_placed_per_device(params, n, capsys):
    """Replica i lives on jax.devices()[i % count] — params and cache
    both — and sharing a device is said out loud."""
    from repro.launch.serve import make_fleet_engines
    engines = make_fleet_engines(CFG, params, n, max_slots=2,
                                 max_seq_len=64)
    devs = jax.devices()
    for i, eng in enumerate(engines):
        homes = {d for leaf in jax.tree.leaves((eng.params, eng.cache))
                 for d in leaf.devices()}
        assert homes == {devs[i % len(devs)]}
    shared = n > len(devs)
    assert ("share" in capsys.readouterr().out) == shared


def test_serve_cli_fleet_of_ssm_replicas(monkeypatch, capsys):
    """``serve --instances 2`` on an arch with no attention layers still
    serves: the dense replicas share the default device, said out loud."""
    import sys
    from repro.launch import serve
    monkeypatch.setattr(serve, "setup_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--arch", "mamba2-780m", "--reduced", "--n", "3",
        "--policy", "fcfs", "--instances", "2"])
    serve.main()
    out = capsys.readouterr().out
    assert "2 replicas share the default device" in out
    assert "mode=fleet2:least-loaded" in out and "attainment=" in out


def test_mesh_engine_serves_without_recompiling_dispatch(params):
    """The warm-up feed carries the dispatch output's sharding, so a
    mesh-placed engine (a fleet replica) compiles no decode step while
    serving — every width it needs was compiled by ``start``."""
    from repro.launch.mesh import make_host_mesh
    eng = _engine(params, mesh=make_host_mesh(tp=1))
    loop = ServeLoop(eng, "fcfs")
    prompts = _prompts(4, seed=7)
    loop.start(warm_lengths=[len(p) for p in prompts])
    warmed = eng._dispatch_fn._cache_size()
    streams = [loop.submit(p, max_new_tokens=5) for p in prompts]
    loop.serve()
    assert all(len(s.tokens) == 5 for s in streams)
    assert eng._dispatch_fn._cache_size() == warmed
