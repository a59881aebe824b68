"""Paged KV pool: kernel parity, paged-vs-dense decode parity (logits
<= 1e-5 over mixed lengths, incl. quantized KV and GQA), block alloc/free
invariants across admit -> preempt -> re-admit -> finish, and
out-of-blocks admission refusal."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.slo import SLO, Request
from repro.engine.blocks import BlockPool
from repro.engine.engine import Engine
from repro.engine.request import RuntimeRequest
from repro.kernels import ref
from repro.kernels.decode_attention_paged import (decode_attention_paged,
                                                  decode_attention_paged_q8)
from repro.models import ModelConfig, init_cache, init_params
from repro.models.cache import (init_paged_cache, paged_slot_len,
                                quantize_kv)
from repro.models.model import (forward_decode, forward_decode_paged,
                                forward_full, forward_prefill_paged)

CFG = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
                  dtype="float32")


def _rts(n, seed=0, vocab=97, max_new=4, lo=8, hi=40):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        ln = int(rng.integers(lo, hi))
        out.append(RuntimeRequest(
            request=Request(req_id=i, task_type="chat", input_len=ln,
                            slo=SLO(ttft=100.0, tpot=10.0)),
            prompt_tokens=rng.integers(0, vocab, ln).astype(np.int32),
            max_new_tokens=max_new))
    return out


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _kv_major(pages):
    """[N, P, KV, d] -> the pool's kv-head-major [KV, N, P, d]."""
    return jnp.moveaxis(pages, 2, 0)


# ------------------------------------------------------------ kernel level
@pytest.mark.parametrize("b,h,kv,hd,P,npg,window", [
    (1, 4, 4, 32, 16, 4, 0),       # MHA
    (3, 8, 2, 64, 16, 8, 0),       # GQA 4x
    (2, 4, 1, 64, 32, 4, 0),       # MQA
    (2, 8, 2, 64, 16, 4, 24),      # sliding window over a rounded ring
])
def test_paged_kernel_matches_ref(b, h, kv, hd, P, npg, window):
    """Pallas paged flash-decode (interpret) vs the gather oracle, over
    mixed lengths including ring wrap (lengths > ring)."""
    L = P * npg
    nb = 1 + b * npg
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    kp = _kv_major(jax.random.normal(ks[1], (nb, P, kv, hd), jnp.float32))
    vp = _kv_major(jax.random.normal(ks[2], (nb, P, kv, hd), jnp.float32))
    bt = jnp.arange(1, 1 + b * npg, dtype=jnp.int32).reshape(b, npg)
    lengths = jnp.asarray(
        np.linspace(3, L + P, b).astype(np.int32))     # incl. wrapped
    out = decode_attention_paged(q, kp, vp, bt, lengths, window=window,
                                 interpret=True)
    want = ref.decode_attention_paged_ref(q, kp, vp, bt, lengths,
                                          window=window)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)


def test_paged_kernel_matches_dense_kernel_ref():
    """No wrap, full table: paged ref == dense decode ref on the gathered
    cache (the layouts describe the same logical cache)."""
    b, h, kv, hd, P, npg = 2, 8, 2, 64, 16, 8
    L = P * npg
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (1 + b * npg, P, kv, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (1 + b * npg, P, kv, hd), jnp.float32)
    bt = jnp.arange(1, 1 + b * npg, dtype=jnp.int32).reshape(b, npg)
    lengths = jnp.array([40, L], jnp.int32)
    kc = kp[bt].reshape(b, L, kv, hd)
    vc = vp[bt].reshape(b, L, kv, hd)
    want = ref.decode_attention_ref(q, kc, vc, lengths)
    got = ref.decode_attention_paged_ref(q, _kv_major(kp), _kv_major(vp),
                                         bt, lengths)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)


def test_paged_kernel_q8_matches_ref():
    b, h, kv, hd, P, npg = 2, 8, 2, 64, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    kf = jax.random.normal(ks[1], (1 + b * npg, P, kv, hd), jnp.float32)
    vf = jax.random.normal(ks[2], (1 + b * npg, P, kv, hd), jnp.float32)
    kq, ksc, vq, vsc = map(_kv_major, quantize_kv(kf) + quantize_kv(vf))
    bt = jnp.arange(1, 1 + b * npg, dtype=jnp.int32).reshape(b, npg)
    lengths = jnp.array([17, P * npg], jnp.int32)
    out = decode_attention_paged_q8(q, kq, ksc, vq, vsc, bt, lengths,
                                    interpret=True)
    from repro.models.cache import dequantize_kv
    want = ref.decode_attention_paged_ref(
        q, dequantize_kv(kq, ksc).astype(jnp.float32),
        dequantize_kv(vq, vsc).astype(jnp.float32), bt, lengths)
    np.testing.assert_allclose(out, want, atol=5e-3, rtol=5e-3)


# --------------------------------------------------------- cache writers
# (kind, int8 pool with scales, rows S, valid n, span start): token rows
# carry their own (page, offset); a span's come from _prefill_targets
# over a 4-page ring of 16 rows
WRITES = {
    "token-bf16": ("token", False, 5, 5, 0),
    "token-int8": ("token", True, 5, 5, 0),
    "token-dead-slots": ("token", False, 6, 6, 0),
    "span-bf16": ("span", False, 12, 12, 0),
    "span-int8-padded": ("span", True, 12, 7, 0),
    "span-padded": ("span", False, 12, 5, 3),
    "span-wraps-ring": ("span", False, 12, 12, 10),
}


@pytest.mark.parametrize("case", sorted(WRITES))
def test_kv_writes_match_head_window_scatter(case):
    """The K/V writers store what ``pages.at[:, page_ids, offs].set``
    stores, bit for bit, on every page but the null page (where dead
    slots and padded rows collide, and which no live read uses)."""
    from repro.models.cache import (NULL_PAGE, _prefill_targets,
                                    kv_prefill_write, kv_token_write)
    kind, quantized, S, n, start = WRITES[case]
    KV, N, P, d = 2, 9, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(S + n + start), 3)
    vals = jax.random.normal(ks[0], (S, KV, d), jnp.float32)
    if quantized:
        q, s = quantize_kv(vals)
        writes = [(jax.random.randint(ks[1], (KV, N, P, d), -127, 128,
                                      jnp.int8), q),
                  (jax.random.normal(ks[2], (KV, N, P, 1), jnp.bfloat16),
                   s)]
    else:
        writes = [(jax.random.normal(ks[1], (KV, N, P, d), jnp.bfloat16),
                   vals)]
    bt = jnp.asarray([3, 7, 1, 5], jnp.int32)
    if kind == "token":
        page_ids = jnp.asarray([4, 8, 2, 6, 3, 2][:S], jnp.int32)
        offs = jnp.asarray([0, 3, 1, 2, 3, 0][:S], jnp.int32)
        if case == "token-dead-slots":
            page_ids = page_ids.at[1:4].set(NULL_PAGE)
            offs = offs.at[1:4].set(2)
        write = jax.jit(lambda p, x: kv_token_write(p, x, page_ids, offs))
    else:
        page_ids, offs = _prefill_targets(bt, S, P, n, start)
        write = jax.jit(lambda p, x: kv_prefill_write(p, x, bt, n, start))
    live = np.arange(N) != NULL_PAGE
    for pages, x in writes:
        want = pages.at[:, page_ids, offs].set(
            jnp.swapaxes(x, 0, 1).astype(pages.dtype))
        got = write(pages, x)
        assert got.dtype == pages.dtype and got.shape == pages.shape
        np.testing.assert_array_equal(np.asarray(got)[:, live],
                                      np.asarray(want)[:, live])


# ------------------------------------------------------------- model level
def _identity_tables(B, npg):
    return jnp.arange(1, 1 + B * npg, dtype=jnp.int32).reshape(B, npg)


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_decode_matches_dense_logits(params, quantized):
    """Prefill both layouts from the same prompts (mixed lengths), then
    several decode steps: logits agree to <= 1e-5 (GQA arch; exact for
    the unquantized full-attention layout)."""
    B, msl, P = 3, 128, 16
    lens = [9, 24, 57]
    rng = np.random.default_rng(0)
    dense = init_cache(CFG, B, msl, quantized=quantized)
    npg = paged_slot_len(CFG, msl, P) // P
    paged = init_paged_cache(CFG, B, msl, 1 + B * npg, P,
                             quantized=quantized)
    paged["block_tables"] = _identity_tables(B, npg)
    for s, n in enumerate(lens):
        toks = jnp.asarray(rng.integers(0, 97, (1, n)).astype(np.int32))
        d1 = init_cache(CFG, 1, msl, quantized=quantized)
        _, d1, _ = forward_full(params, CFG, tokens=toks, cache=d1)
        for li in range(CFG.num_layers):
            for k in dense["layers"][li]:
                dense["layers"][li][k] = \
                    dense["layers"][li][k].at[s].set(d1["layers"][li][k][0])
        dense["pos"] = dense["pos"].at[s].set(n)
        _, paged = forward_prefill_paged(params, CFG, tokens=toks,
                                         cache=paged, slot=s, length=n)
    nxt = jnp.asarray(rng.integers(0, 97, (B, 1)).astype(np.int32))
    for _ in range(3):
        gd, dense = forward_decode(params, CFG, tokens=nxt, cache=dense)
        gp, paged = forward_decode_paged(params, CFG, tokens=nxt,
                                         cache=paged)
        np.testing.assert_allclose(gp, gd, atol=1e-5, rtol=1e-5)
        nxt = jnp.argmax(gd[:, -1], -1)[:, None]


def test_paged_engine_matches_dense_engine(params):
    """End-to-end: the paged engine generates the same greedy tokens as
    the dense engine (full-attention arch: bit-identical attended sets)."""
    a = Engine(CFG, params, max_slots=3, max_seq_len=128).run_fcfs(
        _rts(5, seed=3))
    b = Engine(CFG, params, max_slots=3, max_seq_len=128,
               paged=False).run_fcfs(_rts(5, seed=3))
    assert all(a[i]["tokens"] == b[i]["tokens"] for i in a)


def test_engine_kernel_mode_is_per_engine(params):
    """Each engine closes over its own kernel mode: an engine decoding
    through the (interpreted) Pallas paged kernel generates the same
    greedy tokens as one on the gather reference, built after it in the
    same process, and neither changes the other's declared mode."""
    a = Engine(CFG, params, max_slots=3, max_seq_len=128,
               kernel_mode="interpret")
    b = Engine(CFG, params, max_slots=3, max_seq_len=128, kernel_mode="ref")
    ra = a.run_fcfs(_rts(4, seed=11))
    rb = b.run_fcfs(_rts(4, seed=11))
    assert (a.kernel_mode, b.kernel_mode) == ("interpret", "ref")
    assert all(ra[i]["tokens"] == rb[i]["tokens"] for i in ra)


@pytest.mark.parametrize("mode,tp,want", [
    ("interpret", 1, "interpret"),
    ("ref", 1, "ref"),
    (None, 1, "ref"),              # no TPU backend in the tests
    (None, 4, "ref"),              # tp > 1 declares the gather reference
    ("ref", 4, "ref"),
    ("pallas", 4, ValueError),     # the kernel is not partitioned yet
    ("auto", 1, ValueError),       # no such mode
])
def test_resolve_kernel_mode(mode, tp, want):
    from repro.kernels.ops import resolve_mode
    if want is ValueError:
        with pytest.raises(ValueError):
            resolve_mode(mode, tp)
    else:
        assert resolve_mode(mode, tp) == want


def test_paged_engine_chunked_matches_dense(params):
    """Chunked in-place prefill: same greedy tokens as the dense engine's
    whole-prompt path."""
    a = Engine(CFG, params, max_slots=3, max_seq_len=128,
               chunked_prefill=16).run_fcfs(_rts(5, seed=4))
    b = Engine(CFG, params, max_slots=3, max_seq_len=128,
               paged=False).run_fcfs(_rts(5, seed=4))
    assert all(a[i]["tokens"] == b[i]["tokens"] for i in a)


def test_paged_chunked_quantized_cache_roundtrip(params):
    """Chunked continuation on an int8 paged cache keeps the scale pages
    and dequantizes the prefix: decode after chunked prefill stays close
    to decode after whole-prompt prefill (quantization drift only)."""
    from repro.models.model import forward_chunk_paged
    P, msl = 16, 128
    npg = paged_slot_len(CFG, msl, P) // P
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 24), 0, 97)

    def fresh():
        c = init_paged_cache(CFG, 1, msl, 1 + npg, P, quantized=True)
        c["block_tables"] = _identity_tables(1, npg)
        return c
    a = fresh()
    _, a = forward_prefill_paged(params, CFG, tokens=toks, cache=a,
                                 slot=0, length=24)
    b = fresh()
    for i in range(0, 24, 8):
        _, b = forward_chunk_paged(params, CFG, tokens=toks[:, i:i + 8],
                                   cache=b, slot=0)
    assert "k_scale" in b["layers"][0] and "v_scale" in b["layers"][0]
    nxt = jnp.array([[5]])
    ga, _ = forward_decode_paged(params, CFG, tokens=nxt, cache=a)
    gb, _ = forward_decode_paged(params, CFG, tokens=nxt, cache=b)
    assert float(jnp.max(jnp.abs(ga - gb))) < 0.15


def test_preempt_policy_caps_block_need_at_ring(params):
    """Regression: pending_blocks is capped at the slot ring like the
    engine's own reservation — a windowed request whose prompt + output
    exceed the ring must still be admitted by SLOPreemptPolicy."""
    from repro.core.latency_model import PAPER_TABLE2
    cfg = ModelConfig(name="w", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
                      dtype="float32", sliding_window=32)
    p = init_params(jax.random.PRNGKey(4), cfg)
    rng = np.random.default_rng(4)
    rt = RuntimeRequest(
        request=Request(req_id=0, task_type="chat", input_len=30,
                        slo=SLO(ttft=100.0, tpot=10.0), output_len=40),
        prompt_tokens=rng.integers(0, 97, 30).astype(np.int32),
        max_new_tokens=40)
    rt.request.predicted_output_len = 40
    eng = Engine(cfg, p, max_slots=1, max_seq_len=128, block_size=16)
    out = eng.run_policy([rt], "slo-preempt", model=PAPER_TABLE2)
    assert len(out[0]["tokens"]) == 40


def test_chunked_prefill_warms_and_profiles_chunks(params):
    """Regression: prefill_chunked must warm the chunk jit per chunk size
    (compile time off the engine clock) and feed every chunk timing to
    the profiler."""
    from repro.core.profiler import LatencyProfiler
    prof = LatencyProfiler()
    eng = Engine(CFG, params, max_slots=2, max_seq_len=128,
                 chunked_prefill=16, profiler=prof)
    rts = _rts(2, seed=5, lo=33, hi=40)     # >= 3 chunks each
    n_chunks = sum(-(-rt.input_len // 16) for rt in rts)
    eng.run_fcfs(rts)
    assert len(prof.prefill_samples) == n_chunks
    assert any(k[0] == "chunk" for k in eng._warm)
    # compile happened off the clock: chunk samples are msec-scale, not
    # the tens-of-msec a tiny-model jit compile costs
    assert max(t for _, _, t in prof.prefill_samples) < 1.0


# ----------------------------------------------------------- block pool
def test_block_pool_invariants():
    pool = BlockPool(8)
    assert pool.total == 7 and pool.available == 7
    a = pool.alloc(3)
    b = pool.alloc(4)
    assert pool.available == 0 and pool.in_use == 7
    assert 0 not in a + b                   # null page never handed out
    with pytest.raises(RuntimeError):
        pool.alloc(1)
    pool.free(a)
    assert pool.available == 3
    with pytest.raises(ValueError):
        pool.free(a)                        # double free
    pool.free(b)
    assert pool.available == 7 and pool.in_use == 0


def test_engine_blocks_across_admit_preempt_readmit_finish(params):
    """Alloc/free invariants over the full lifecycle: blocks are held
    exactly while a request holds a slot, re-admission re-allocates, and
    the pool drains back to full after every request finishes.  Runs
    with the prefix cache off: exclusive PR-5 ownership semantics (with
    prefix sharing, the index deliberately retains pages — covered in
    test_prefix.py)."""
    eng = Engine(CFG, params, max_slots=2, max_seq_len=128,
                 prefix_cache=False)
    total = eng.pool.total
    rt = _rts(1, seed=6)[0]
    eng.prefill(rt, 0)
    held = eng.pool.in_use
    assert held == eng._blocks_needed(rt) > 0
    assert np.asarray(eng.cache["block_tables"])[0].max() > 0
    eng.preempt(rt)
    assert eng.pool.in_use == 0 and eng.pool.available == total
    assert np.asarray(eng.cache["block_tables"])[0].max() == 0
    eng.prefill(rt, 1)                      # re-admit on another slot
    assert eng.pool.in_use == eng._blocks_needed(rt)
    while rt.phase.name != "FINISHED":
        eng.decode_round()
    assert eng.pool.in_use == 0 and eng.pool.available == total
    assert np.asarray(eng.cache["block_tables"]).max() == 0


def test_engine_out_of_blocks_admission_refusal(params):
    """A pool covering one request at a time: the second admission is
    refused until the first finishes — both still complete, sequentially."""
    rts = _rts(2, seed=7, lo=30, hi=36, max_new=4)
    need = -(-(36 + 4) // 16)
    eng = Engine(CFG, params, max_slots=2, max_seq_len=128,
                 num_blocks=need + 1,       # + null page: fits ONE request
                 prefix_cache=False)        # exclusive-pool drain semantics
    out = eng.run_fcfs(rts)
    assert all(len(v["tokens"]) == 4 for v in out.values())
    # sequential service: 1 could only start after 0 finished
    assert out[1]["ttft"] > out[0]["e2e"] * 0.5
    assert eng.pool.available == eng.pool.total


def test_engine_unservable_request_raises(params):
    """A request whose prompt + output budget exceeds the whole pool is
    refused permanently (ValueError, not a silent stall)."""
    rts = _rts(1, seed=8, lo=60, hi=61, max_new=4)
    eng = Engine(CFG, params, max_slots=2, max_seq_len=128, num_blocks=3)
    with pytest.raises(ValueError, match="KV blocks"):
        eng.run_fcfs(rts)


def test_paged_pool_admits_more_than_dense_at_equal_hbm(params):
    """The headline capacity claim: at the HBM budget of a 2-slot dense
    engine, the paged pool serves a short-prompt mix >= 2x more
    concurrently (slots are cheap; tokens are the budget)."""
    from repro.models.cache import kv_bytes_per_token
    msl = 128
    bpt = kv_bytes_per_token(CFG)
    hbm = 2 * msl * bpt                     # dense: 2 full-length slots
    block_size = 16
    num_blocks = hbm // (block_size * bpt)  # same HBM in pages
    # short-prompt mix: 24-token prompts + 8 output -> 2 blocks each
    rts = _rts(8, seed=9, lo=24, hi=25, max_new=8)
    eng = Engine(CFG, params, max_slots=8, max_seq_len=msl,
                 block_size=block_size, num_blocks=int(num_blocks) + 1)
    concurrent = []
    orig = eng.decode_round

    def counting_round():
        concurrent.append(sum(not f for f in eng.slot_free))
        orig()
    eng.decode_round = counting_round
    out = eng.run_fcfs(rts)
    assert all(len(v["tokens"]) == 8 for v in out.values())
    assert max(concurrent) >= 4             # dense admits 2 at this HBM


def test_scheduler_view_exposes_block_occupancy(params):
    """SchedulerView carries the pool occupancy while requests run."""
    from repro.core.policies import SchedulingPolicy, Decision

    class Probe(SchedulingPolicy):
        views = []

        def decide(self, view):
            Probe.views.append(view)
            return Decision(admit=list(range(min(view.free,
                                                 len(view.pending)))))
    Probe.views = []
    eng = Engine(CFG, params, max_slots=2, max_seq_len=128)
    rts = _rts(4, seed=10)
    for i, rt in enumerate(rts):            # staggered finishes: later
        rt.max_new_tokens = 3 + 3 * i       # views see running requests
    eng.run_policy(rts, Probe())
    assert all(v.total_blocks == eng.pool.total for v in Probe.views)
    assert all(v.block_size == 16 for v in Probe.views)
    busy = [v for v in Probe.views if v.active]
    assert busy, "no view saw active requests"
    assert any(v.free_blocks < v.total_blocks for v in busy)
    assert all(a.blocks_held > 0 for v in busy for a in v.active)
    v = busy[0]
    assert v.blocks_for(17) == 2 and v.pending_blocks(0) > 0


def test_preempt_policy_memory_aware_eviction():
    """On a block-starved view, SLOPreemptPolicy filters admissions to
    the free blocks and evicts the victim freeing the most blocks per
    slack to make a tight arrival fit."""
    from repro.core.latency_model import PAPER_TABLE2
    from repro.core.policies import (SLOPreemptPolicy, SchedulerView,
                                     make_active_view)
    tight = Request(req_id=0, task_type="chat", input_len=32,
                    slo=SLO(ttft=0.2), output_len=8)
    tight.predicted_output_len = 8
    tight.submit_time = 0.0
    victims = []
    for rid, (blocks, out_len) in enumerate([(2, 400), (12, 400)], start=1):
        r = Request(req_id=rid, task_type="code", input_len=16,
                    slo=SLO(e2e=1e4), output_len=out_len)
        r.submit_time = 0.0
        victims.append(make_active_view(
            r, generated=4, remaining=out_len - 4, context_len=20,
            now=0.0, ttft=0.0, e2e_base=0.0, batch=2, model=PAPER_TABLE2,
            blocks_held=blocks))
    view = SchedulerView(pending=(tight,), active=tuple(victims), now=0.0,
                         free=1, max_batch=4, pending_generated=(0,),
                         free_blocks=0, total_blocks=14, block_size=16)
    dec = SLOPreemptPolicy(PAPER_TABLE2).decide(view)
    # a free slot exists but zero free blocks: eviction must free the
    # big-holding victim (index 1), then the arrival is admitted
    assert dec.preempt == [1]
    assert dec.admit == [0]
