"""Shared-prefix KV reuse: refcounted pool semantics (incl. the
hardened PR-5 ``free`` shim), the radix prefix index (match/insert/LRU
eviction), lifecycle invariants under admit -> share -> preempt ->
re-admit -> finish, aliased-prefix logits parity vs dense, zero prefill
FLOPs for the shared span, copy-on-write, the equal-HBM concurrency
win, and the prefill discount through both annealer backends."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.annealing_jax as aj
from repro.core.latency_model import PAPER_TABLE2
from repro.core.objective import calculate_g, fcfs_schedule, \
    linear_request_coefs
from repro.core.slo import SLO, Request, as_arrays
from repro.data.synthetic import (sample_multiturn_requests,
                                  sample_multiturn_token_requests)
from repro.engine.blocks import BlockPool
from repro.engine.engine import Engine
from repro.engine.prefix import RadixPrefixIndex
from repro.engine.request import RuntimeRequest
from repro.models import ModelConfig, init_cache, init_params
from repro.models.cache import (copy_page, init_paged_cache,
                                paged_slot_len)
from repro.models.model import forward_chunk_paged, forward_full, \
    forward_prefill_paged

CFG = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=97,
                  dtype="float32")
P = 16          # block size used throughout


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _rt(prompt, rid=0, max_new=4):
    return RuntimeRequest(
        request=Request(req_id=rid, task_type="chat", input_len=len(prompt),
                        output_len=max_new, slo=SLO(ttft=100.0, tpot=10.0)),
        prompt_tokens=np.asarray(prompt, np.int32), max_new_tokens=max_new)


# --------------------------------------------------------------- block pool
def test_pool_refcount_lifecycle():
    pool = BlockPool(8)
    a = pool.alloc(3)
    assert all(pool.refcount(i) == 1 for i in a)
    assert pool.in_use == 3 and pool.available == 4 and pool.shared == 0
    pool.share(a[:2])
    assert pool.shared == 2 and pool.refcount(a[0]) == 2
    pool.release(a)                     # one owner off each: a[2] frees
    assert pool.in_use == 2 and pool.available == 5
    pool.release(a[:2])                 # last owners: pool drains
    assert pool.in_use == 0 and pool.available == pool.total == 7


def test_pool_share_validates_before_mutating():
    pool = BlockPool(8)
    a = pool.alloc(2)
    with pytest.raises(ValueError, match="not allocated"):
        pool.share([a[0], 99])
    assert pool.refcount(a[0]) == 1     # nothing incremented


def test_pool_release_validates_multiplicity_atomically():
    pool = BlockPool(8)
    a = pool.alloc(1)
    with pytest.raises(ValueError, match="double free"):
        pool.release([a[0], a[0]])      # refcount 1 can't cover x2
    assert pool.refcount(a[0]) == 1 and pool.available == 6


def test_pool_free_shim_rejects_duplicates_and_double_free():
    """The PR-5 API hardened: duplicate ids in one call and double frees
    raise *before* any mutation (the old free() appended to the free
    list mid-walk, so a duplicate corrupted it)."""
    pool = BlockPool(8)
    a = pool.alloc(2)
    with pytest.raises(ValueError, match="listed twice"):
        pool.free([a[0], a[0]])
    # atomic: the failed call freed nothing
    assert pool.in_use == 2 and pool.available == 5
    pool.free(a)
    assert pool.available == pool.total
    with pytest.raises(ValueError, match="double free|not allocated"):
        pool.free([a[0]])
    assert pool.available == pool.total     # free list uncorrupted
    b = pool.alloc(pool.total)              # every id usable exactly once
    assert len(set(b)) == pool.total and 0 not in b


def test_pool_free_shim_warns_on_shared_block():
    pool = BlockPool(8)
    a = pool.alloc(1)
    pool.share(a)
    with pytest.warns(DeprecationWarning, match="shared block"):
        pool.free(a)
    assert pool.refcount(a[0]) == 1     # decremented, not fully freed


# --------------------------------------------------------------- radix index
def _toks(rng, n):
    return rng.integers(0, 97, n).astype(np.int32)


def test_radix_probe_match_insert_roundtrip():
    pool = BlockPool(32)
    idx = RadixPrefixIndex(pool, P)
    rng = np.random.default_rng(0)
    toks = _toks(rng, 4 * P + 5)            # 4 full blocks + ragged tail
    ids = pool.alloc(5)
    assert idx.insert(toks, ids) == 4       # full blocks only
    assert all(pool.refcount(i) == 2 for i in ids[:4])
    assert pool.refcount(ids[4]) == 1       # ragged tail never indexed
    assert idx.probe(toks) == 4 * P
    assert idx.probe(toks, max_tokens=len(toks) - 1) == 4 * P
    assert idx.probe(toks[: 2 * P + 3]) == 2 * P
    assert idx.match(toks[: 3 * P]) == ids[:3]
    assert idx.insert(toks, ids) == 0       # dedup: keys are the content
    # divergence after 2 blocks matches exactly those 2
    div = np.concatenate([toks[: 2 * P], _toks(rng, 2 * P) + 97])
    assert idx.probe(div % 97 + 0) <= 2 * P
    pool.release(ids)                       # owner gone; index retains
    assert idx.reclaimable() == 4
    assert idx.probe(toks) == 4 * P


def test_radix_evict_lru_leaves_first_and_skips_shared():
    pool = BlockPool(32)
    idx = RadixPrefixIndex(pool, P)
    rng = np.random.default_rng(1)
    t1, t2 = _toks(rng, 2 * P), _toks(rng, 2 * P)
    a, b = pool.alloc(2), pool.alloc(2)
    idx.insert(t1, a)
    idx.insert(t2, b)
    pool.release(a)
    pool.release(b)                         # both chains index-only
    idx.match(t1)                           # touch chain 1: chain 2 is LRU
    assert idx.evict(1) == 1
    assert idx.probe(t2) == P               # chain 2 lost its *leaf* only
    assert idx.probe(t1) == 2 * P
    # a chain some request still aliases is never evicted
    held = idx.match(t2)                    # [b[0]]
    pool.share(held)
    assert idx.evict(10) == 2               # only t1's chain + t2's root
    assert idx.probe(t2) == P and len(idx) == 1
    pool.release(held)
    assert idx.evict(10) == 1
    assert pool.available == pool.total and len(idx) == 0


def test_radix_lifecycle_property_never_leaks():
    """Randomized admit -> share -> finish/preempt -> evict churn: the
    pool never leaks or double-frees (available + in_use == total after
    every op), shared pages survive any single owner's exit, and a full
    drain (release actives + clear index) restores the empty pool."""
    rng = np.random.default_rng(7)
    pool = BlockPool(64)
    idx = RadixPrefixIndex(pool, P)
    families = [_toks(rng, 6 * P) for _ in range(3)]
    active = []                             # (tokens, blocks)

    def invariant():
        assert pool.available + pool.in_use == pool.total
        for _, blocks in active:
            assert all(pool.refcount(i) >= 1 for i in blocks)

    for _ in range(300):
        op = rng.integers(0, 4)
        if op == 0 and len(active) < 6:     # admit, aliasing what's cached
            fam = families[rng.integers(0, len(families))]
            n = int(rng.integers(P, 6 * P))
            toks = np.concatenate([fam[:n], _toks(rng, 5)])
            need = -(-len(toks) // P) + 1
            matched = idx.match(toks, max_tokens=len(toks) - 1)
            pool.share(matched)
            short = (need - len(matched)) - pool.available
            if short > 0:
                idx.evict(short)
            if need - len(matched) > pool.available:
                pool.release(matched)       # refused: full rollback
            else:
                active.append((toks, matched + pool.alloc(
                    need - len(matched))))
        elif op == 1 and active:            # finish: publish then release
            toks, blocks = active.pop(rng.integers(0, len(active)))
            idx.insert(toks, blocks)
            pool.release(blocks)
        elif op == 2 and active:            # preempt: release only
            _, blocks = active.pop(rng.integers(0, len(active)))
            pool.release(blocks)
        else:
            idx.evict(int(rng.integers(0, 3)))
        invariant()
    for _, blocks in active:
        pool.release(blocks)
    idx.clear()
    assert pool.available == pool.total and pool.in_use == 0


# ------------------------------------------------------------- model level
def test_aliased_prefix_logits_match_dense(params):
    """A suffix prefill over aliased prefix pages (pos preset to the
    cached span, padded rows routed to the null page) produces the same
    last-token logits as a dense full-prompt forward."""
    msl = 128
    npg = paged_slot_len(CFG, msl, P) // P
    paged = init_paged_cache(CFG, 2, msl, 1 + 2 * npg, P)
    rng = np.random.default_rng(3)
    a = _toks(rng, 57)                      # 3 full blocks + 9 tail
    b = np.concatenate([a[:48], _toks(rng, 9)])
    rowA = np.zeros(npg, np.int32)
    rowA[:4] = np.arange(1, 5)
    rowB = np.zeros(npg, np.int32)
    rowB[:3] = np.arange(1, 4)              # alias A's prefix pages
    rowB[3] = 5                             # fresh page for the suffix
    paged["block_tables"] = jnp.asarray(np.stack([rowA, rowB]))
    _, paged = forward_prefill_paged(params, CFG, tokens=jnp.asarray(a[None]),
                                     cache=paged, slot=0, length=57)
    paged["pos"] = paged["pos"].at[1].set(48)
    suf = np.zeros((1, 16), np.int32)       # padded beyond the 9 real rows
    suf[0, :9] = b[48:]
    got, paged = forward_chunk_paged(params, CFG, tokens=jnp.asarray(suf),
                                     cache=paged, slot=1, length=9)
    dense = init_cache(CFG, 1, msl)
    want, _, _ = forward_full(params, CFG, tokens=jnp.asarray(b[None]),
                              cache=dense)
    np.testing.assert_allclose(np.asarray(got[0, 0]),
                               np.asarray(want[0, len(b) - 1]),
                               atol=1e-5, rtol=1e-5)
    assert int(paged["pos"][1]) == 57


def test_copy_page_copies_every_attention_layer(params):
    cache = init_paged_cache(CFG, 1, 128, 8, P)
    k0 = cache["layers"][0]["k"]
    cache["layers"][0]["k"] = k0.at[:, 2].set(1.5)   # kv-head-major
    cache = copy_page(cache, 2, 5)
    for layer in cache["layers"]:
        for v in layer.values():
            np.testing.assert_array_equal(np.asarray(v[:, 5]),
                                          np.asarray(v[:, 2]))


# ------------------------------------------------------------ engine level
class _Rec:
    """Profiler stand-in recording prefill token counts."""

    def __init__(self):
        self.prefill = []

    def observe_prefill(self, b, l, t):
        self.prefill.append(int(l))

    def observe_decode(self, b, l, t):
        pass


def test_engine_zero_prefill_flops_for_shared_span(params):
    """The headline reuse claim: a request whose prefix is cached
    prefills only its unique suffix (observed prefill work == suffix
    length), and still generates token-identical output vs an unshared
    engine."""
    rng = np.random.default_rng(4)
    shared = _toks(rng, 48)
    p0 = np.concatenate([shared, _toks(rng, 9)])
    p1 = np.concatenate([shared, _toks(rng, 13)])
    rec = _Rec()
    eng = Engine(CFG, params, max_slots=4, max_seq_len=256,
                 temperature=0.0, profiler=rec)
    out = eng.run_fcfs([_rt(p0, 0), _rt(p1, 1)])
    assert rec.prefill == [len(p0), len(p1) - 48]
    assert out[0]["cached"] == 0 and out[1]["cached"] == 48
    ref = Engine(CFG, params, max_slots=4, max_seq_len=256,
                 temperature=0.0, prefix_cache=False).run_fcfs(
        [_rt(p0, 0), _rt(p1, 1)])
    for k in out:
        assert out[k]["tokens"] == ref[k]["tokens"]
    assert eng.prefix_stats()["hit_rate"] > 0


def test_engine_multiturn_second_turn_hits_cache(params):
    """A turn-2 prompt extending a finished conversation aliases the
    pages the index retained at finish (prompt + generated tokens)."""
    rng = np.random.default_rng(5)
    p0 = _toks(rng, 57)
    eng = Engine(CFG, params, max_slots=2, max_seq_len=256,
                 temperature=0.0)
    out = eng.run_fcfs([_rt(p0, 0, max_new=4)])
    turn2 = np.concatenate([p0, np.asarray(out[0]["tokens"][:-1], np.int32),
                            _toks(rng, 7)])
    out2 = eng.run_fcfs([_rt(turn2, 1, max_new=4)])
    # 57 prompt + 3 written generated = 60 -> 3 full blocks cached
    assert out2[1]["cached"] == 48
    ref = Engine(CFG, params, max_slots=2, max_seq_len=256,
                 temperature=0.0, prefix_cache=False).run_fcfs(
        [_rt(turn2, 1, max_new=4)])
    assert out2[1]["tokens"] == ref[1]["tokens"]


def test_engine_shared_pages_survive_sharers_eviction(params):
    """Preempting a request that aliases cached pages releases only its
    reference: the survivor and the index keep the pages, and the
    preempted request re-matches them on re-admission."""
    rng = np.random.default_rng(6)
    shared = _toks(rng, 48)
    a = _rt(np.concatenate([shared, _toks(rng, 5)]), 0, max_new=8)
    b = _rt(np.concatenate([shared, _toks(rng, 7)]), 1, max_new=8)
    eng = Engine(CFG, params, max_slots=2, max_seq_len=256,
                 temperature=0.0)
    eng.prefill(a, 0)
    eng.prefill(b, 1)
    assert b.cached_tokens == 48
    shared_ids = eng._slot_blocks[1][:3]
    assert shared_ids == eng._slot_blocks[0][:3]
    assert all(eng.pool.refcount(i) == 3 for i in shared_ids)  # a, b, index
    eng.preempt(b)
    assert all(eng.pool.refcount(i) == 2 for i in shared_ids)  # a, index
    assert eng.prefix.probe(shared) == 48   # still cached
    eng.prefill(b, 1)                       # re-admit: matches again
    assert b.cached_tokens >= 48
    while a.phase.name != "FINISHED" or b.phase.name != "FINISHED":
        eng.decode_round()
    # only the index owns the cached pages now; accounting is exact
    assert eng.pool.available + eng.pool.in_use == eng.pool.total
    assert eng.pool.in_use == len(eng.prefix)
    eng.prefix.clear()
    assert eng.pool.available == eng.pool.total


def test_engine_prefix_admits_strictly_more_at_equal_hbm(params):
    """Acceptance: at the same block budget, prefix sharing runs
    strictly more requests concurrently than the PR-5 exclusive pool on
    a shared-prompt mix (5 blocks/request exclusive vs 2 unique)."""
    rng = np.random.default_rng(8)
    shared = _toks(rng, 48)
    prompts = [np.concatenate([shared, _toks(rng, 9)]) for _ in range(4)]

    def peak(prefix_cache):
        eng = Engine(CFG, params, max_slots=8, max_seq_len=256,
                     temperature=0.0, num_blocks=15,
                     prefix_cache=prefix_cache)
        seen = []
        orig = eng.decode_round

        def counting():
            seen.append(sum(not f for f in eng.slot_free))
            orig()
        eng.decode_round = counting
        out = eng.run_fcfs([_rt(p, i, max_new=8)
                            for i, p in enumerate(prompts)])
        assert all(len(v["tokens"]) == 8 for v in out.values())
        return max(seen)

    assert peak(True) > peak(False)


def test_engine_cow_splits_shared_frontier_block(params):
    """Copy-on-write guard: if a slot's write frontier lands in a page
    another owner shares (manufactured here — block-aligned matching
    makes it unreachable through admission), the page is split before
    the decode write and the phantom owner's refcount survives."""
    rng = np.random.default_rng(9)
    rt = _rt(_toks(rng, 20), 0, max_new=8)
    eng = Engine(CFG, params, max_slots=2, max_seq_len=256,
                 temperature=0.0)
    eng.prefill(rt, 0)
    bi = rt.input_len // P                  # frontier block
    old = eng._slot_blocks[0][bi]
    eng.pool.share([old])                   # phantom co-owner
    eng.decode_round()
    assert eng.cow_copies == 1
    new = eng._slot_blocks[0][bi]
    assert new != old
    assert eng.pool.refcount(old) == 1      # phantom keeps its page
    assert eng.pool.refcount(new) == 1
    assert int(eng.cache["block_tables"][0, bi]) == new
    eng.pool.release([old])
    assert eng.pool.available + eng.pool.in_use == eng.pool.total


def test_chunked_prefill_skips_cached_span(params):
    """The chunked discipline starts its chunk walk mid-sequence at the
    cached boundary and stays token-identical."""
    rng = np.random.default_rng(10)
    shared = _toks(rng, 48)
    prompts = [np.concatenate([shared, _toks(rng, 9 + i)])
               for i in range(2)]
    rec = _Rec()
    eng = Engine(CFG, params, max_slots=4, max_seq_len=256,
                 temperature=0.0, chunked_prefill=16, profiler=rec)
    # serialized runs: the second prompt claims its pages after the
    # first is indexed, so its chunk walk starts at the cached boundary
    # (prefills staged in the *same* tick advance in parallel under
    # chunk-as-tick and can only alias spans indexed when they start)
    out = dict(eng.run_fcfs([_rt(prompts[0], 0)]))
    out.update(eng.run_fcfs([_rt(prompts[1], 1)]))
    # request 1 prefilled only its 10-token unique suffix, in one chunk
    assert sum(rec.prefill) == len(prompts[0]) + (len(prompts[1]) - 48)
    ref_eng = Engine(CFG, params, max_slots=4, max_seq_len=256,
                     temperature=0.0, chunked_prefill=16,
                     prefix_cache=False)
    ref = dict(ref_eng.run_fcfs([_rt(prompts[0], 0)]))
    ref.update(ref_eng.run_fcfs([_rt(prompts[1], 1)]))
    for k in out:
        assert out[k]["tokens"] == ref[k]["tokens"]


# --------------------------------------------------------------- pricing
def test_annealer_backends_price_cached_prefix_identically():
    """numpy calculate_g and the jitted _eval_g agree (<= 1e-6 under
    x64) on a multi-turn workload with nonzero cached_prefix, and both
    actually discount: zeroing the cached column changes G."""
    reqs = sample_multiturn_requests(4, turns=3, seed=11)
    for r in reqs:
        r.predicted_output_len = r.output_len
        r.slo = dataclasses.replace(r.slo, ttft=0.2, tpot=0.02)
    arrays = as_arrays(reqs)
    assert arrays["cached_prefix"].max() > 0
    n = len(reqs)
    perm, bid = fcfs_schedule(n, 4)
    g_np = calculate_g(arrays, PAPER_TABLE2, perm, bid)
    bnd = np.zeros(n, np.int32)
    bnd[np.searchsorted(bid, np.unique(bid))] = 1
    with jax.enable_x64(True):
        reqc = aj._pack(arrays, PAPER_TABLE2, n)
        g_jax, _ = aj._eval_g(reqc, jnp.asarray(perm, jnp.int32),
                              jnp.asarray(bnd, jnp.int32))
        assert abs(float(g_jax) - g_np) <= 1e-6 * max(abs(g_np), 1.0)
    flat = dict(arrays)
    flat["cached_prefix"] = np.zeros(n)
    g_flat = calculate_g(flat, PAPER_TABLE2, perm, bid)
    assert g_np != g_flat


def test_prefill_coefs_discounted_by_cached_prefix():
    """linear_request_coefs — the shared contract behind the numpy
    incremental evaluator AND the jax packer — prices prefill at the
    unique length only; decode terms keep the full context."""
    base = Request(req_id=0, task_type="chat", input_len=100,
                   output_len=20, slo=SLO(ttft=1.0, tpot=0.05))
    hit = dataclasses.replace(base, req_id=1, cached_prefix=64)
    coefs = linear_request_coefs(as_arrays([base, hit]), PAPER_TABLE2)
    assert coefs["pA"][1] < coefs["pA"][0]      # cheaper prefill
    assert coefs["pC"][1] < coefs["pC"][0]
    assert coefs["eA"][1] < coefs["eA"][0]      # exec inherits it
    assert coefs["tA"][1] == coefs["tA"][0]     # decode: full context
    assert coefs["tC"][1] == coefs["tC"][0]
    m = PAPER_TABLE2
    assert m.exec_time(1, 100, 20, cached=64) < m.exec_time(1, 100, 20)
    assert m.ttft_exec(1, 100, cached=64) < m.ttft_exec(1, 100)


# -------------------------------------------------------------- workloads
def test_multiturn_request_generator_shapes():
    reqs = sample_multiturn_requests(3, turns=3, seed=0, block_size=16)
    assert len(reqs) == 9
    times = [r.arrival_time for r in reqs]
    assert times == sorted(times)
    assert [r.req_id for r in reqs] == list(range(9))
    assert any(r.cached_prefix > 0 for r in reqs)
    for r in reqs:
        assert 0 <= r.cached_prefix < r.input_len
        assert r.cached_prefix % 16 == 0


def test_multiturn_token_generator_shares_prefixes():
    out = sample_multiturn_token_requests(4, turns=2, vocab=97, seed=0,
                                          system_prompt_len=48,
                                          n_system_prompts=2)
    assert len(out) == 8
    by_id = {r.req_id: (r, t) for r, t in out}
    assert sorted(by_id) == list(range(8))
    sys_heads = {tuple(t[:48]) for _, t in out}
    assert len(sys_heads) == 2              # two shared system prompts
    for r, t in out:
        assert r.input_len == len(t)
    # within a conversation, turn 2's prompt extends turn 1's: every
    # turn-1 prompt (4 conversations) is a strict prefix of another
    prompts = [t for _, t in out]
    extended = sum(
        1 for t in prompts
        if any(len(s) > len(t) and np.array_equal(s[:len(t)], t)
               for s in prompts))
    assert extended >= 4
