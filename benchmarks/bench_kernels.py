"""Kernel microbenchmarks: Pallas (interpret) correctness deltas vs oracle
and XLA-reference timings on CPU.  On real TPU hardware the same harness
times the compiled kernels.

The ``paged_*`` rows are the paged-KV-pool acceptance metrics (also
written to ``experiments/bench/BENCH_paged.json`` for the perf
trajectory):

* ``paged_decode`` — µs/token at equal live tokens: dense decode over
  its worst-case-length slot vs paged decode gathering live pages only.
* ``paged_commit`` — per-prefill slot-commit cost as the pool grows:
  the dense layout's whole-slot ``.at[slot].set`` scatter is O(pool);
  the paged in-place page scatter (jit buffer donation) stays flat.
* ``paged_capacity`` — concurrent admissions at a fixed HBM budget on a
  short-prompt mix: the paged pool prices HBM by live tokens, the dense
  layout by ``max_slots × max_seq_len``.

The ``prefix_*`` rows are the shared-prefix KV reuse acceptance metrics
(written to ``experiments/bench/BENCH_prefix.json``): engine prefill
time/tokens vs prefix hit rate, and concurrent admissions at a 1 GiB KV
budget with 90 %-shared prompts vs the exclusive pool.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import RESULTS_DIR, emit, timeit
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan


def _paged_rows(quick: bool):
    """Paged-pool acceptance rows; returns (csv_rows, json_payload)."""
    from repro.kernels.decode_attention_paged import decode_attention_paged
    from repro.models import ModelConfig
    from repro.models.cache import kv_bytes_per_token

    rows, payload = [], {}
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    B, H, KV, hd, P = 4, 8, 2, 64, 16
    live = 256 if quick else 512            # live tokens per sequence
    Lmax = 2 * live                         # the dense slot's worst case
    npg = live // P
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    kc = jax.random.normal(ks[1], (B, Lmax, KV, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, Lmax, KV, hd), jnp.float32)
    nv = jnp.full((B,), live, jnp.int32)
    null = jnp.zeros((1, P, KV, hd), jnp.float32)
    # kv-head-major pools [KV, N, P, hd], page 0 the null page
    kp = jnp.moveaxis(jnp.concatenate(
        [null, kc[:, :live].reshape(-1, P, KV, hd)], 0), 2, 0)
    vp = jnp.moveaxis(jnp.concatenate(
        [null, vc[:, :live].reshape(-1, P, KV, hd)], 0), 2, 0)
    bt = jnp.arange(1, 1 + B * npg, dtype=jnp.int32).reshape(B, npg)

    # --- decode µs/token at equal live tokens (CPU: both XLA ref paths;
    # on TPU the same harness times the compiled Pallas kernels)
    fd = jax.jit(ref.decode_attention_ref)
    fp = jax.jit(ref.decode_attention_paged_ref)
    fd(q, kc, vc, nv).block_until_ready()
    fp(q, kp, vp, bt, nv).block_until_ready()
    _, td = timeit(lambda: fd(q, kc, vc, nv).block_until_ready(), repeat=5)
    _, tp = timeit(lambda: fp(q, kp, vp, bt, nv).block_until_ready(),
                   repeat=5)
    # correctness of the Pallas kernel on this exact shape
    err = float(jnp.max(jnp.abs(
        decode_attention_paged(q, kp, vp, bt, nv, interpret=True)
        - fp(q, kp, vp, bt, nv))))
    ratio = tp / td
    rows.append([f"paged_decode_live{live}", round(tp * 1e6, 1),
                 f"vs_dense_slot{Lmax}={ratio:.3f};interpret_err={err:.1e}"])
    payload["decode"] = {"live_tokens": live, "dense_slot_len": Lmax,
                         "dense_us": td * 1e6, "paged_us": tp * 1e6,
                         "paged_vs_dense": ratio, "interpret_err": err}

    # --- per-prefill slot-commit cost vs pool size
    S = 64                                  # committed prompt tokens
    one = jax.random.normal(ks[1], (S, KV, hd), jnp.float32)
    commit = {}
    for slots in ((4, 16) if quick else (8, 64)):
        # dense: whole-slot scatter into [slots, Lmax, KV, hd]
        dense_pool = jnp.zeros((slots, Lmax, KV, hd))
        slot_kv = jnp.zeros((Lmax, KV, hd)).at[:S].set(one)
        fdc = jax.jit(lambda p, o: p.at[0].set(o))
        fdc(dense_pool, slot_kv).block_until_ready()
        _, tdc = timeit(
            lambda: fdc(dense_pool, slot_kv).block_until_ready(), repeat=5)
        # paged: O(S) scatter into [KV, slots*npages, P, hd], donated
        npages = Lmax // P
        fpc = jax.jit(lambda p, o, pg, of:
                      p.at[:, pg, of].set(jnp.swapaxes(o, 0, 1)),
                      donate_argnums=0)
        pg = jnp.repeat(jnp.arange(1, 1 + S // P, dtype=jnp.int32), P)
        of = jnp.tile(jnp.arange(P, dtype=jnp.int32), S // P)
        paged_pool = jnp.zeros((KV, 1 + slots * npages, P, hd))
        paged_pool = fpc(paged_pool, one, pg, of)       # warm (donates)
        def run():
            pool = jnp.zeros((KV, 1 + slots * npages, P, hd))
            pool.block_until_ready()
            _, t = timeit(
                lambda: fpc(pool, one, pg, of).block_until_ready(),
                repeat=1)
            return t
        tpc = min(run() for _ in range(5))
        commit[slots] = {"dense_us": tdc * 1e6, "paged_us": tpc * 1e6}
        rows.append([f"paged_commit_slots{slots}", round(tpc * 1e6, 1),
                     f"dense_us={tdc * 1e6:.1f};"
                     f"paged_vs_dense={tpc / tdc:.4f}"])
    lo, hi = sorted(commit)
    payload["commit"] = {
        "tokens": S, "per_slots": commit,
        "paged_growth": commit[hi]["paged_us"] / commit[lo]["paged_us"],
        "dense_growth": commit[hi]["dense_us"] / commit[lo]["dense_us"]}
    rows.append(["paged_commit_growth",
                 round(payload["commit"]["paged_growth"], 3),
                 f"pool_x{hi // lo};"
                 f"dense_growth={payload['commit']['dense_growth']:.2f}"])

    # --- admission capacity at a fixed HBM budget (short-prompt mix)
    cfg = ModelConfig(name="cap", family="dense", num_layers=16,
                      d_model=2048, num_heads=16, num_kv_heads=4, d_ff=8192,
                      vocab_size=32000, dtype="bfloat16")
    bpt = kv_bytes_per_token(cfg)
    max_seq = 4096
    dense_slots = 8
    hbm = dense_slots * max_seq * bpt       # the dense engine's KV budget
    blocks = hbm // (P * bpt)
    rng = np.random.default_rng(0)
    admitted = 0
    free = int(blocks)
    while True:                             # short prompts + bounded output
        need = -(-int(rng.integers(64, 512) + 256) // P)
        if need > free:
            break
        free -= need
        admitted += 1
    rows.append(["paged_capacity", admitted,
                 f"dense_slots={dense_slots};hbm_gb={hbm / 2**30:.2f};"
                 f"capacity_x={admitted / dense_slots:.2f}"])
    payload["capacity"] = {"hbm_bytes": int(hbm),
                           "dense_concurrent": dense_slots,
                           "paged_concurrent": admitted,
                           "ratio": admitted / dense_slots}
    return rows, payload


def _prefix_rows(quick: bool):
    """Shared-prefix KV reuse acceptance rows; returns
    (csv_rows, json_payload):

    * ``prefix_prefill_hit*`` — engine prefill wall time and computed
      tokens vs prefix hit rate on a shared-system-prompt mix: at 90 %
      shared the engine prefills only the unique tail.
    * ``prefix_capacity_1gib`` — concurrent admissions at a 1 GiB KV
      budget with 90 %-shared prompts: refcounted aliasing vs the PR-5
      exclusive pool.
    """
    from repro.core.slo import SLO, Request
    from repro.engine.engine import Engine
    from repro.engine.request import RuntimeRequest
    from repro.models import ModelConfig, init_params
    from repro.models.cache import kv_bytes_per_token

    rows, payload = [], {}
    cfg = ModelConfig(name="bench-tiny", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=128, dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    total = 160                             # prompt tokens per request
    n_req = 4 if quick else 8

    class _Rec:                             # prefill (tokens, seconds)
        def __init__(self):
            self.samples = []

        def observe_prefill(self, b, l, t):
            self.samples.append((int(l), float(t)))

        def observe_decode(self, b, l, t):
            pass

    def run(shared_frac):
        rng = np.random.default_rng(0)
        shared_len = (int(total * shared_frac) // 16) * 16
        head = rng.integers(0, 128, shared_len)
        rts = []
        for i in range(n_req):
            toks = np.concatenate(
                [head, rng.integers(0, 128, total - shared_len)]).astype(
                np.int32)
            rts.append(RuntimeRequest(
                request=Request(req_id=i, task_type="chat",
                                input_len=total,
                                slo=SLO(ttft=60.0, tpot=10.0)),
                prompt_tokens=toks, max_new_tokens=4))
        rec = _Rec()
        eng = Engine(cfg, params, max_slots=n_req, max_seq_len=512,
                     temperature=0.0, profiler=rec)
        eng.run_fcfs(rts)
        toks_done = sum(l for l, _ in rec.samples)
        t_pref = sum(t for _, t in rec.samples)
        return (toks_done, t_pref, eng.prefix_stats()["hit_rate"])

    base_toks, base_t, _ = run(0.0)
    payload["prefill"] = {"prompt_tokens": total, "requests": n_req,
                          "sweep": {}}
    for frac in (0.5, 0.9):
        toks_done, t_pref, hit = run(frac)
        payload["prefill"]["sweep"][str(frac)] = {
            "hit_rate": hit, "prefill_tokens": toks_done,
            "prefill_s": t_pref,
            "tokens_vs_unshared": toks_done / base_toks,
            "time_vs_unshared": t_pref / base_t if base_t else 0.0}
        rows.append([f"prefix_prefill_hit{int(frac * 100)}",
                     round(t_pref * 1e6, 1),
                     f"hit_rate={hit:.3f};"
                     f"tokens={toks_done}/{base_toks};"
                     f"time_vs_unshared={t_pref / base_t:.3f}"])

    # --- capacity at 1 GiB with 90% shared prefixes (host arithmetic,
    # production-scale config): exclusive pool vs refcounted aliasing
    big = ModelConfig(name="cap", family="dense", num_layers=16,
                      d_model=2048, num_heads=16, num_kv_heads=4,
                      d_ff=8192, vocab_size=32000, dtype="bfloat16")
    P = 16
    bpt = kv_bytes_per_token(big)
    blocks = (1 << 30) // (P * bpt)         # 1 GiB of KV pages
    prompt, out_budget = 2048, 256
    shared_blocks = (int(prompt * 0.9) // P)
    need_full = -(-(prompt + out_budget) // P)
    need_unique = need_full - shared_blocks
    excl = int(blocks // need_full)
    shared = 0
    free = int(blocks)
    while free >= (need_full if shared == 0 else need_unique):
        free -= need_full if shared == 0 else need_unique
        shared += 1
    rows.append(["prefix_capacity_1gib", shared,
                 f"exclusive={excl};shared_x={shared / max(excl, 1):.2f};"
                 f"prompt={prompt};shared_frac=0.9"])
    payload["capacity_1gib"] = {
        "blocks": int(blocks), "prompt_tokens": prompt,
        "output_budget": out_budget, "shared_frac": 0.9,
        "exclusive_concurrent": excl, "shared_concurrent": shared,
        "ratio": shared / max(excl, 1)}
    return rows, payload


def main(quick: bool = False):
    rows = []
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 5)

    # flash attention
    B, S, H, KV, hd = 2, 256, 8, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    r, t_ref = timeit(lambda: jax.block_until_ready(
        ref.flash_attention_ref(q, k, v)), repeat=2)
    o, t_pal = timeit(lambda: jax.block_until_ready(
        flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)),
        repeat=1)
    err = float(jnp.max(jnp.abs(o - r)))
    rows.append(["flash_attention_256", round(t_ref * 1e6, 1),
                 f"interpret_err={err:.2e}"])

    # decode attention
    L = 2048 if not quick else 512
    kc = jax.random.normal(ks[1], (B, L, KV, hd), jnp.float32)
    vc = jax.random.normal(ks[2], (B, L, KV, hd), jnp.float32)
    qd = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    nv = jnp.array([L // 2, L], jnp.int32)
    r, t_ref = timeit(lambda: jax.block_until_ready(
        ref.decode_attention_ref(qd, kc, vc, nv)), repeat=2)
    o, _ = timeit(lambda: jax.block_until_ready(
        decode_attention(qd, kc, vc, nv, block_k=256, interpret=True)),
        repeat=1)
    err = float(jnp.max(jnp.abs(o - r)))
    rows.append([f"decode_attention_L{L}", round(t_ref * 1e6, 1),
                 f"interpret_err={err:.2e}"])

    # ssd scan
    b, s, nh, hdim, ds = 2, 256, 4, 64, 32
    x = jax.random.normal(ks[0], (b, s, nh, hdim), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    Bm = jax.random.normal(ks[3], (b, s, ds))
    Cm = jax.random.normal(ks[4], (b, s, ds))
    (yr, _), t_ref = timeit(lambda: jax.tree.map(
        jax.block_until_ready, ref.ssd_ref(x, dt, A, Bm, Cm, chunk=64)),
        repeat=2)
    (y, _), _ = timeit(lambda: jax.tree.map(
        jax.block_until_ready,
        ssd_scan(x, dt, A, Bm, Cm, chunk=64, interpret=True)), repeat=1)
    err = float(jnp.max(jnp.abs(y - yr)))
    rows.append([f"ssd_scan_{s}", round(t_ref * 1e6, 1),
                 f"interpret_err={err:.2e}"])

    # paged KV pool: decode / slot-commit / capacity acceptance rows
    paged_rows, payload = _paged_rows(quick)
    rows.extend(paged_rows)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, "BENCH_paged.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"# saved {path}")

    # shared-prefix reuse: prefill-vs-hit-rate / 1 GiB capacity rows
    prefix_rows, prefix_payload = _prefix_rows(quick)
    rows.extend(prefix_rows)
    path = os.path.join(RESULTS_DIR, "BENCH_prefix.json")
    with open(path, "w") as f:
        json.dump(prefix_payload, f, indent=2)
    print(f"# saved {path}")

    emit(rows, ["name", "us_per_call", "derived"], "kernels")
    return rows


if __name__ == "__main__":
    main()
