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
