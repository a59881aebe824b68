"""The trace reduction: intervals, attribution and op names, on events
shaped as a TPU v5e trace gives them."""
from onchip import trace_reduce as tr
from onchip.trace_reduce import Event


def test_union_gaps_and_total():
    iv = tr.union([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert iv == [(0, 3), (5, 9)]
    assert tr.total(iv) == 7
    assert tr.gaps(iv, 0, 12) == [(3, 5), (9, 12)]
    assert tr.gaps(iv, 1, 6) == [(3, 5)]
    assert tr.clip(iv, 2, 6) == [(2, 3), (5, 6)]


def test_busy_counts_overlap_once_and_only_inside_the_window():
    evs = [Event("a", 0, 10), Event("b", 5, 15), Event("c", 30, 40)]
    assert tr.total(tr.busy(evs, 2, 35)) == 13 + 5


def test_time_by_name_folds_and_clips():
    evs = [Event("jit_f(1)", 0, 10), Event("jit_f(2)", 20, 25),
           Event("jit_g(1)", 8, 30)]
    got = tr.time_by_name(evs, 5, 22, key=tr.module_name)
    assert got == {"jit_f": 5 + 2, "jit_g": 14}


def test_gaps_are_labelled_by_the_innermost_open_span():
    spans = [Event("bench.tick", 0, 100), Event("bench.decide", 10, 20),
             Event("bench.prefill", 50, 90)]
    gaps = [(12, 18), (40, 48), (60, 61), (200, 230)]
    got = tr.labelled_gaps(gaps, spans, top=3)
    assert got == [("<none>", 30), ("bench.tick", 8), ("bench.decide", 6)]


def test_op_names_fold_to_the_instruction():
    """``XLA Ops`` events carry the HLO text; the kernel keeps its name."""
    assert tr.op_name("%decode_attention_paged.81 = bf16[64,8,2,128] "
                      "custom-call(s32[64,256] %copy-done.33)") \
        == "decode_attention_paged"
    assert tr.op_name("%fusion.16 = bf16[4,2048] fusion(...)") == "fusion"
    assert tr.op_name("%copy-start.10") == "copy-start"
    assert tr.module_name("jit__decode_dispatch_paged(1408)") \
        == "jit__decode_dispatch_paged"


# a decode step every 10 ns from 0 to 100: the host's span starts 1 ns
# before the device's 6 ns program run
STEPS = [Event("jit__decode_dispatch_paged(7)", t, t + 6)
         for t in range(0, 100, 10)]
SPANS = [Event("bench.dispatch", t - 1, t) for t in range(0, 100, 10)]


def test_the_traced_part_ends_at_the_last_device_event():
    assert tr.traced(STEPS[:4], -5, 120) == (-5, 36)
    # an event that starts before the window, or runs past its end
    assert tr.traced(STEPS, 15, 95) == (15, 95)
    assert tr.traced(STEPS[:1], 15, 95) == (15, 15)


def _part(modules, spans=SPANS, steps=10, dropped=0):
    from onchip import harness
    rec = harness.Record(arch=None, spec=None, peak={},
                         device="/device:TPU:0",
                         decode_ctx=[[i] for i in range(steps)])
    rec.trace = tr.Trace(ops={rec.device: modules},
                         modules={rec.device: modules}, spans=spans,
                         dropped={rec.device: dropped})
    runs = harness._traced_part(rec, -5, 120)
    return rec.window_ns, runs, [c[0] for c in rec.decode_ctx]


def test_a_whole_trace_keeps_its_window():
    assert _part(STEPS) == ((-5, 120), (10, 0), list(range(10)))


def test_a_dropped_tail_ends_the_window_and_the_steps_counted():
    # the device kept four runs; the host dispatched ten
    assert _part(STEPS[:4]) == ((-5, 36), (4, 0), [0, 1, 2, 3])
    # the fifth step's span started before the fourth run ended, but its
    # own run was lost: it is not counted
    late = SPANS[:4] + [Event("bench.dispatch", 33, 34)] + SPANS[5:]
    assert _part(STEPS[:4], late) == ((-5, 36), (4, 0), [0, 1, 2, 3])
    # a plane that reports dropped buffers ends at its last event too
    assert _part(STEPS, dropped=3)[0] == (-5, 96)


def test_step_records_pair_with_their_spans_or_the_run_fails():
    import pytest
    from onchip import harness
    spans = [Event("bench.dispatch", t, t + 2) for t in (30, 10, 50)] \
        + [Event("bench.prefill", 12, 14), Event("bench.dispatch", 200, 201)]
    assert harness._step_starts(spans, "bench.dispatch", 0, 100, 3) \
        == [10, 30, 50]
    with pytest.raises(RuntimeError, match="not whole"):
        harness._step_starts(spans, "bench.dispatch", 0, 100, 4)
    with pytest.raises(RuntimeError, match="not whole"):
        _part(STEPS, steps=11)
