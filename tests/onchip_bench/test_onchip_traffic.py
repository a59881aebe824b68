"""The benchmark's traffic generator and window arithmetic (CPU)."""
import json

import numpy as np
import pytest

from conftest import ROOT
from onchip import traffic

MIX = ROOT / "benchmarks" / "onchip" / "traffic" / "mixed-slo.json"


@pytest.fixture
def mix():
    return traffic.load_mix(MIX)


def _key(reqs):
    return [(r.task_type, r.due, r.input_len, r.output_len,
             r.prompt.tobytes()) for r in reqs]


def test_same_seed_same_requests(mix):
    a = traffic.generate(mix, 2**40 + 7, 30, 1000, max_total=4096)
    b = traffic.generate(mix, 2**40 + 7, 30, 1000, max_total=4096)
    assert _key(a) == _key(b)


def test_seeds_offer_the_same_sizes_and_arrivals(mix):
    """Every seed offers the same work: the classes, lengths and due
    times are the base draw's, in the ramp and in the window; only the
    prompt tokens are the seed's."""
    a = traffic.generate(mix, 1, 30, 1000, max_total=4096)
    b = traffic.generate(mix, 2**33 + 1, 30, 1000, max_total=4096)
    assert len(a) == len(b) > 0
    assert [k[:4] for k in _key(a)] == [k[:4] for k in _key(b)]
    assert all(x.prompt.tobytes() != y.prompt.tobytes()
               for x, y in zip(a, b) if x.input_len > 4)
    assert {r.task_type for r in a} == {"code", "chat"}
    assert 0 < sum(r.due >= mix.ramp_s for r in a) < len(a)


def test_arrivals_cover_ramp_and_window_at_the_rate(mix):
    reqs = traffic.generate(mix, 3, 30, 1000, max_total=4096)
    span = mix.ramp_s + 30
    assert all(0 < r.due < span for r in reqs)
    assert abs(len(reqs) / span - mix.rate) < 0.35 * mix.rate


def test_lengths_fit_the_slot(mix):
    reqs = traffic.generate(mix, 4, 30, 1000, max_total=300)
    assert all(r.input_len + r.output_len <= 300 for r in reqs)
    assert all(r.input_len >= 1 and r.output_len >= 1 for r in reqs)
    assert all(len(r.prompt) == r.input_len for r in reqs)


def test_slo_tags_come_from_the_profiles(mix):
    reqs = traffic.generate(mix, 5, 30, 1000, max_total=4096)
    for r in reqs:
        if r.task_type == "code":
            assert r.slo == {"e2e": 30.0, "ttft": None, "tpot": None}
        else:
            assert r.slo == {"e2e": None, "ttft": 10.0, "tpot": 0.05}


def _req(idx, due, out, slo=None, task="code"):
    return traffic.Req(idx=idx, task_type=task, due=due, input_len=4,
                       output_len=out, slo=slo or {"e2e": 30.0, "ttft": None,
                                                   "tpot": None},
                       prompt=np.zeros(4, np.int32))


def test_window_counts_only_requests_due_in_it():
    reqs = [_req(0, 0.5, 2), _req(1, 1.5, 2), _req(2, 2.5, 2)]
    outs = {0: traffic.Outcome([0.6, 0.7]), 1: traffic.Outcome([1.6, 1.7]),
            2: traffic.Outcome([2.6, 2.8])}
    m = traffic.window_metrics(reqs, outs, ramp_s=1.0, seconds=2.0,
                               t_end=3.0)
    assert m["attempted"] == 2 and m["failed"] == 0
    assert m["slo_attainment"] == 100.0
    # request 0 (ramp) is served and not counted; its tokens are outside
    assert m["output_tok_s"] == 4 / 2.0
    assert m["n_gaps"] == 2
    assert m["tbt_p50_ms"] == pytest.approx(150.0)


def test_drain_cap_and_rejection_count_as_failed_and_missed():
    reqs = [_req(0, 1.0, 3), _req(1, 1.2, 2), _req(2, 1.4, 2)]
    outs = {0: traffic.Outcome([1.1, 1.2]),                # cut at the cap
            1: traffic.Outcome([], rejected=True),
            2: traffic.Outcome([1.5, 1.6])}
    m = traffic.window_metrics(reqs, outs, ramp_s=1.0, seconds=1.0,
                               t_end=4.0)
    assert m["attempted"] == 3 and m["failed"] == 2
    assert m["slo_attainment"] == pytest.approx(100.0 / 3)
    # the rejected request's TTFT counts up to the end of the run
    assert m["ttft_p95_ms"] > 2000.0


def test_tokens_after_the_window_are_left_out_of_rate_and_tbt():
    reqs = [_req(0, 1.0, 4)]
    outs = {0: traffic.Outcome([1.5, 1.9, 2.1, 2.3])}
    m = traffic.window_metrics(reqs, outs, ramp_s=1.0, seconds=1.0,
                               t_end=2.5)
    assert m["output_tok_s"] == 2.0 and m["n_gaps"] == 1
    assert m["failed"] == 0 and m["slo_attainment"] == 100.0


@pytest.mark.parametrize("ttft,tpot,met", [(1.0, 0.04, True),
                                           (11.0, 0.04, False),
                                           (1.0, 0.06, False)])
def test_interactive_class_is_judged_on_ttft_and_tpot(ttft, tpot, met):
    slo = {"e2e": None, "ttft": 10.0, "tpot": 0.05}
    assert traffic.meets_slo(slo, e2e=100.0, ttft=ttft, tpot=tpot) is met


def test_mix_files_name_known_profiles_and_processes():
    d = ROOT / "benchmarks" / "onchip" / "traffic"
    for path in d.glob("*.json"):
        m = traffic.load_mix(path)
        assert m.rate > 0 and m.ramp_s > 0 and m.drain_cap_s > 0
        for p in m.profiles:
            traffic.load_profile(p)
        assert json.loads(path.read_text())["discipline"] == "stall"
