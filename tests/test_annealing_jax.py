"""The jitted annealer: incremental-Δ scorer vs the full-evaluate oracle
(1e-6 under x64), padding/masking invariance, vmap-multi-instance vs
per-instance equivalence, config validation, and the jax backend of the
online re-anneal policy.  See docs/annealer.md for the contract being
pinned here."""
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

import repro.core.annealing_jax as aj  # noqa: E402
from repro.core import PAPER_TABLE2, SAParams, as_arrays, evaluate  # noqa: E402
from repro.core.annealing_jax import (JaxSAConfig,  # noqa: E402
                                      priority_mapping_jax,
                                      priority_mapping_multi_jax)
from repro.core.objective import (fcfs_schedule,  # noqa: E402
                                  linear_request_coefs,
                                  sorted_by_e2e_schedule)
from repro.data.synthetic import sample_requests  # noqa: E402

# One shared shape/config across tests keeps jit recompilation (the
# dominant cost of this file) to a handful of cache entries.
N, MB = 13, 4
CFG = JaxSAConfig(iters=50, num_chains=2)


def _contended(reqs):
    """Tighten SLOs so schedules mix met and unmet requests."""
    for r in reqs:
        r.slo = dataclasses.replace(
            r.slo,
            e2e=r.slo.e2e * 0.2 if r.slo.e2e else None,
            ttft=r.slo.ttft * 0.02 if r.slo.ttft else None,
            tpot=r.slo.tpot * 0.5 if r.slo.tpot else None)
        r.predicted_output_len = r.output_len
    return reqs


def _arrays(seed, n=N, regime="contended"):
    reqs = sample_requests(n, seed=seed)
    if regime == "contended":
        _contended(reqs)
    else:
        for r in reqs:
            r.predicted_output_len = r.output_len
    return as_arrays(reqs)


def _np_g(arrays, perm_pad, bnd_pad, n):
    perm = np.asarray(perm_pad)[:n]
    bnd = np.asarray(bnd_pad)[:n]
    bid = np.cumsum(bnd.astype(np.int64)) - 1
    return evaluate(arrays, PAPER_TABLE2, perm, bid)


@pytest.fixture(scope="module")
def jitted():
    """Jitted internals at the shared (N, MB) shape."""
    nv = jnp.int32(N)
    return {
        "cand": jax.jit(lambda reqc, perm, bnd, stats, op, i, j:
                        aj._candidate(reqc, perm, bnd, stats, op, i, j,
                                      nv, MB)),
        "apply": jax.jit(aj._apply),
        "agg": jax.jit(lambda stats: aj._agg(stats, MB)),
        "agg_delta": jax.jit(lambda stats, sidx, rows:
                             aj._agg_delta(stats, sidx, rows, MB)),
        "build": jax.jit(lambda reqc, perm, bnd:
                         aj._build_stats(reqc, perm, bnd, MB)),
        "eval_g": jax.jit(aj._eval_g),
    }


@pytest.mark.parametrize("regime", ["contended", "loose"])
def test_incremental_scorer_matches_oracle_to_1e6(jitted, regime):
    """The contract: after any chain of valid moves, the incremental
    stats score the schedule to 1e-6 of BOTH the in-jit full objective
    and the numpy ``evaluate`` oracle (exact in x64), the met counts
    agree exactly, and no row goes stale (rebuild-from-scratch parity).
    """
    with jax.enable_x64(True):
        for seed in (0, 1):
            arrays = _arrays(seed, regime=regime)
            reqc = aj._pack(arrays, PAPER_TABLE2, aj._pad_len(N))
            assert reqc.dtype == jnp.float64
            _, perm, bnd = aj._starts(reqc, jnp.int32(N), MB)
            stats = jitted["build"](reqc, perm, bnd)
            rng = np.random.default_rng(seed)
            applied = 0
            for _ in range(60):
                op = jnp.int32(rng.integers(0, 3))
                i = jnp.int32(rng.integers(1, N))
                j = jnp.int32(rng.integers(0, N))
                ok, _, upd = jitted["cand"](reqc, perm, bnd, stats,
                                            op, i, j)
                g_delta, met_delta = jitted["agg_delta"](stats, upd[4],
                                                         upd[5])
                if not bool(ok):
                    continue
                applied += 1
                perm, bnd, stats = jitted["apply"](perm, bnd, stats, upd,
                                                   jnp.bool_(True))
                g_inc, met_inc = jitted["agg"](stats)
                g_full, met_full = jitted["eval_g"](reqc, perm, bnd)
                ev = _np_g(arrays, perm, bnd, N)
                scale = max(ev.G, 1e-9)
                assert abs(float(g_delta) - float(g_inc)) <= 1e-12
                assert int(met_delta) == int(met_inc)
                assert abs(float(g_inc) - float(g_full)) <= 1e-9 * scale
                assert abs(float(g_inc) - ev.G) <= 1e-6 * scale
                assert int(met_inc) == int(met_full) == ev.n_met
                fresh = jitted["build"](reqc, perm, bnd)
                for got, want in zip(stats, fresh):
                    np.testing.assert_allclose(np.asarray(got),
                                               np.asarray(want))
            assert applied > 20          # the move stream was exercised


def test_scorer_padding_invariance():
    """Masked padding must not change the objective: the same instance
    packed at two pad lengths scores identically (and equals numpy)."""
    with jax.enable_x64(True):
        arrays = _arrays(3)
        p0, b0 = fcfs_schedule(N, MB)
        for pad in (16, 32):
            reqc = aj._pack(arrays, PAPER_TABLE2, pad)
            perm = jnp.asarray(
                np.concatenate([p0, np.arange(N, pad)]), jnp.int32)
            bnd = jnp.asarray(np.concatenate(
                [b0 != np.concatenate([[-1], b0[:-1]]),
                 np.ones(pad - N, bool)]))
            stats = aj._build_stats(reqc, perm, bnd, MB)
            g, met = aj._agg(stats, MB)
            g_full, met_full = aj._eval_g(reqc, perm, bnd)
            ev = _np_g(arrays, perm, bnd, N)
            assert abs(float(g) - ev.G) <= 1e-6 * max(ev.G, 1e-9)
            assert abs(float(g_full) - ev.G) <= 1e-6 * max(ev.G, 1e-9)
            assert int(met) == int(met_full) == ev.n_met


def test_linear_request_coefs_shared_contract():
    """The packed coefficient matrix is exactly the Python backend's
    linear-in-b terms (one contract, two consumers)."""
    arrays = _arrays(5)
    coefs = linear_request_coefs(arrays, PAPER_TABLE2)
    reqc = np.asarray(aj._pack(arrays, PAPER_TABLE2, aj._pad_len(N)))
    for col, key in ((aj._EA, "eA"), (aj._EC, "eC"), (aj._PA, "pA"),
                     (aj._PC, "pC"), (aj._TA, "tA"), (aj._TC, "tC")):
        np.testing.assert_allclose(reqc[:N, col], coefs[key], rtol=1e-6)
    assert (reqc[N:, aj._VALID] == 0).all()
    assert (reqc[:N, aj._VALID] == 1).all()


def test_incremental_anneal_matches_full_anneal_invariants():
    """End to end, both scoring paths return valid schedules that never
    lose to either Algorithm 1 starting solution, and report G on the
    oracle scale."""
    for seed in (0, 1):
        arrays = _arrays(seed, n=16)
        p0, b0 = fcfs_schedule(16, MB)
        ps, bs = sorted_by_e2e_schedule(arrays, PAPER_TABLE2, MB)
        g_start = max(evaluate(arrays, PAPER_TABLE2, p0, b0).G,
                      evaluate(arrays, PAPER_TABLE2, ps, bs).G)
        for inc in (True, False):
            perm, bid, g = priority_mapping_jax(
                arrays, PAPER_TABLE2, MB, CFG, seed=seed, incremental=inc)
            ev = evaluate(arrays, PAPER_TABLE2, perm, bid)
            assert sorted(perm.tolist()) == list(range(16))
            assert np.bincount(bid).max() <= MB
            assert ev.G >= g_start * (1 - 1e-5)
            assert abs(ev.G - g) <= 2e-3 * max(g, 1e-12)  # f32 report


def test_vmap_multi_matches_per_instance_chains():
    """One vmapped (instances × chains) program must equal running each
    padded instance through the single-instance chain runner with the
    same per-instance keys — the padding/masking does the work of the
    per-instance loop."""
    sizes = (9, 16, 5)
    arrays_list = [_arrays(100 + k, n=n) for k, n in enumerate(sizes)]
    multi = priority_mapping_multi_jax(arrays_list, PAPER_TABLE2, MB, CFG,
                                       seed=7)
    pad = aj._pad_len(max(sizes))
    base = jax.random.PRNGKey(7)
    for i, (arrays, n) in enumerate(zip(arrays_list, sizes)):
        reqc = aj._pack(arrays, PAPER_TABLE2, pad)
        keys = jax.random.split(jax.random.fold_in(base, i),
                                CFG.num_chains)
        perms, bnds, fs = aj._run_chains(keys, reqc, jnp.int32(n), MB,
                                         CFG, True)
        best = int(jnp.argmax(fs))
        perm, bid = aj._extract(perms[best], bnds[best], n)
        m_perm, m_bid, m_g = multi[i]
        np.testing.assert_array_equal(m_perm, perm)
        np.testing.assert_array_equal(m_bid, bid)
        assert m_g == pytest.approx(float(fs[best]), rel=1e-6)
        # and the result is a valid schedule for the instance
        assert sorted(m_perm.tolist()) == list(range(n))
        assert np.bincount(m_bid).max() <= MB


def test_multi_handles_empty_and_ragged():
    arrays_list = [_arrays(0, n=6), as_arrays([]), _arrays(1, n=16)]
    out = priority_mapping_multi_jax(arrays_list, PAPER_TABLE2, MB, CFG,
                                     seed=0)
    assert len(out) == 3
    assert out[1][0].size == 0 and out[1][2] == 0.0
    for (perm, bid, _), n in zip((out[0], out[2]), (6, 16)):
        assert sorted(perm.tolist()) == list(range(n))


def test_jax_config_and_args_validated():
    with pytest.raises(ValueError, match="num_chains"):
        JaxSAConfig(num_chains=0)
    with pytest.raises(ValueError, match="iters"):
        JaxSAConfig(iters=0)
    with pytest.raises(ValueError, match="tau"):
        JaxSAConfig(tau=1.0)
    with pytest.raises(ValueError, match="temperatures"):
        JaxSAConfig(T0=0.0)
    with pytest.raises(ValueError, match="zero proposals"):
        JaxSAConfig(T0=100.0, T_thres=200.0)
    with pytest.raises(ValueError, match="max_batch"):
        priority_mapping_jax(_arrays(0), PAPER_TABLE2, 0, CFG)
    with pytest.raises(ValueError, match="max_batch"):
        priority_mapping_multi_jax([_arrays(0)], PAPER_TABLE2, -1, CFG)


def test_config_from_sa_params_preserves_budget():
    """SAParams.iters is a TOTAL proposal budget under the default
    budget_mode="global"; the jitted iters-per-level must not inflate it
    by the level count."""
    p = SAParams(iters=100)                      # global budget
    cfg = aj.config_from_sa_params(p)
    total = cfg.n_levels * cfg.iters
    assert total <= 3 * p.iters                  # same order, not ~63x
    assert cfg.iters >= 1
    plvl = SAParams(iters=100, budget_mode="per_level")
    assert aj.config_from_sa_params(plvl).iters == 100
    with pytest.raises(ValueError, match="ablation"):
        aj.config_from_sa_params(SAParams(moves=(2,)))
    with pytest.raises(ValueError, match="ablation"):
        aj.config_from_sa_params(SAParams(acceptance="greedy"))
    # the scheduler front end validates at construction too
    from repro.core import SLOAwareScheduler
    with pytest.raises(ValueError, match="ablation"):
        SLOAwareScheduler(PAPER_TABLE2, use_jax=True,
                          sa_params=SAParams(acceptance="greedy"))


def test_reanneal_policy_jax_backend():
    """The v2 policy stack runs on the jitted annealer backend: the
    ``slo-reanneal:jax`` registry key drives the event core end to end
    and admits a permutation of the pending queue."""
    from repro.core.online import simulate_online
    from repro.core.policies import make

    rng = np.random.default_rng(3)
    reqs = sample_requests(14, seed=8)
    t = 0.0
    for r in reqs:
        t += rng.exponential(0.3)
        r.arrival_time = t
        r.predicted_output_len = r.output_len
    pol = make("slo-reanneal:jax", model=PAPER_TABLE2, max_batch=MB,
               sa_params=SAParams(seed=0, iters=CFG.iters))
    assert pol.backend == "jax"
    res = simulate_online(reqs, PAPER_TABLE2, MB, pol)
    assert res.n == 14
    with pytest.raises(ValueError, match="backend"):
        make("slo-reanneal", model=PAPER_TABLE2, max_batch=MB,
             backend="tpu")
    # jit-unsupported ablation params fail at construction, not on the
    # first admission event mid-run
    with pytest.raises(ValueError, match="ablation"):
        make("slo-reanneal:jax", model=PAPER_TABLE2, max_batch=MB,
             sa_params=SAParams(acceptance="greedy"))


def test_property_scorer_parity_random_schedules(jitted):
    """Hypothesis sweep (optional dep): arbitrary valid boundary layouts
    and permutations — incremental stats == full objective == numpy."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=25, deadline=None)
    @hyp.given(seed=st.integers(0, 2**31 - 1),
               regime=st.sampled_from(["contended", "loose"]))
    def run(seed, regime):
        rng = np.random.default_rng(seed)
        arrays = _arrays(seed % 7, regime=regime)
        # random permutation + random boundaries respecting max_batch
        p = rng.permutation(N)
        cuts, pos = [True], 1
        run_len = 1
        while pos < N:
            new = bool(rng.integers(0, 2)) or run_len >= MB
            cuts.append(new)
            run_len = 1 if new else run_len + 1
            pos += 1
        pad = aj._pad_len(N)
        reqc = aj._pack(arrays, PAPER_TABLE2, pad)
        perm = jnp.asarray(np.concatenate([p, np.arange(N, pad)]),
                           jnp.int32)
        bnd = jnp.asarray(np.concatenate(
            [np.asarray(cuts), np.ones(pad - N, bool)]))
        stats = jitted["build"](reqc, perm, bnd)
        g, met = jitted["agg"](stats)
        g_full, met_full = jitted["eval_g"](reqc, perm, bnd)
        ev = _np_g(arrays, perm, bnd, N)
        scale = max(ev.G, 1e-9)
        assert abs(float(g) - float(g_full)) <= 2e-5 * scale    # f32
        assert abs(float(g) - ev.G) <= 2e-5 * scale
        assert int(met) == int(met_full) == ev.n_met

    run()
