"""Jittable simulated-annealing priority mapper — batched and incremental.

The paper runs Algorithm 1 in Python on the host.  Here the whole anneal
is a single ``jax.lax`` program so Algorithm 2's per-instance mapping can
run as one jitted batch on the accelerator host: ``vmap`` over PRNG keys
yields independent tempering chains, and :func:`priority_mapping_multi_jax`
adds a second ``vmap`` over *instances* (padding ragged instance loads to
one fixed shape), which amortizes scheduler overhead across the fleet and
keeps it off the Python critical path.

Schedule representation (fixed N, with ``n_valid <= N`` real requests —
positions ``>= n_valid`` hold padding pinned as tail singletons that never
mix with real batches and are masked out of the objective):

  perm [N] int32  — request index per priority position
  bnd  [N] bool   — batch boundary *before* each position (bnd[0] = True)

Moves mirror Algorithm 1: shift a boundary right (squeeze into previous
iteration), shift left / open a new one (delay into next iteration), swap
two positions.  Proposals violating the max-batch constraint are no-ops.

Two scoring paths share one proposal stream:

* ``incremental=False`` — the oracle: every proposal re-evaluates the full
  Eq. 2 objective with segment ops over all N positions (:func:`_eval_g`).
* ``incremental=True`` (default) — the incremental-Δ fast path, the jitted
  port of ``objective.IncrementalEvaluator``.  The ``lax.while_loop``
  state carries per-batch segment aggregates, indexed by batch *start
  position*: the member SLO slacks **sorted ascending** (the largest
  batch wait under which each member still meets its SLO), the structural
  and valid-member sizes, Σ exec, and the batch duration.  A proposal
  rebuilds only the <= 3 touched rows (one vmapped O(max_batch) gather +
  sort over the precomputed linear-in-b request coefficients,
  ``objective.linear_request_coefs``) and scores the candidate without
  materializing it: the wait prefix cache is one ``cumsum`` over batch
  durations with the touched entries overridden, and each batch's met
  count is its valid-member count minus a batched ``searchsorted`` of its
  wait into the sorted slack row (lowered as a fused compare-reduce —
  the same rank).  The *logical* work is the Python evaluator's
  O(batch + n_batches·log b); under fixed jit shapes the scoring is a
  vectorized O(N·max_batch) compare-reduce plus O(N) prefix ops, so the
  win over the full objective is constant-factor and flat in N — every
  N-wide gather, sort, bincount and segment scatter leaves the
  per-proposal path (~3-6x at N >= 128 on CPU, see bench_overhead).
  Accepted rows are committed (and rejected rows reverted) by sparse
  scatters, so the hot loop never pays an O(N) select.

Both paths are cross-checked against the numpy ``objective.evaluate``
oracle (to 1e-6 under x64 — see tests/test_annealing_jax.py and
docs/annealer.md for the contract).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.objective import linear_request_coefs

# Column layout of the per-request coefficient matrix ``reqc`` [N, 11]:
# linear-in-batch-size latency terms (shared contract with the Python
# IncrementalEvaluator via objective.linear_request_coefs), the SLO class
# h, the three SLO budgets, and the padding mask.
_EA, _EC, _PA, _PC, _TA, _TC, _H, _SE, _ST, _SP, _VALID = range(11)
_NCOLS = 11


@dataclasses.dataclass(frozen=True)
class JaxSAConfig:
    """Anneal hyper-parameters (validated — invalid values used to turn
    every proposal into a silent no-op instead of failing loudly)."""
    T0: float = 500.0
    T_thres: float = 20.0
    iters: int = 100
    tau: float = 0.95
    num_chains: int = 8

    def __post_init__(self):
        if self.num_chains < 1:
            raise ValueError(
                f"num_chains must be >= 1, got {self.num_chains}")
        if self.iters < 1:
            raise ValueError(f"iters must be >= 1, got {self.iters}")
        if self.T0 <= 0 or self.T_thres <= 0:
            raise ValueError(
                f"temperatures must be positive, got T0={self.T0}, "
                f"T_thres={self.T_thres}")
        if self.T_thres > self.T0:
            raise ValueError(
                f"T_thres must be <= T0 (the anneal would run zero "
                f"proposals), got T0={self.T0}, T_thres={self.T_thres}")
        if not 0.0 < self.tau < 1.0:
            raise ValueError(f"tau must be in (0, 1), got {self.tau}")

    @property
    def n_levels(self) -> int:
        """Temperature levels under the schedule (>= 1 by validation)."""
        levels, T = 0, self.T0
        while T >= self.T_thres:
            levels += 1
            T *= self.tau
        return levels


def config_from_sa_params(params, num_chains: int = 8) -> JaxSAConfig:
    """Map a Python-annealer ``SAParams`` onto the jitted annealer.

    ``iters`` needs care: the jitted loop always runs ``iters`` proposals
    per temperature level (the Python ``budget_mode="per_level"``),
    whereas the Python default ``budget_mode="global"`` treats ``iters``
    as the TOTAL proposal budget.  A naive copy would inflate a global
    budget by the level count (~63x under the default schedule), so a
    global budget is spread across the levels instead.  ``moves`` and
    ``acceptance`` ablation knobs have no jitted counterpart (the JAX
    path always uses the full move set with Metropolis acceptance) and
    are rejected rather than silently dropped.
    """
    if tuple(params.moves) != (0, 1, 2) or params.acceptance != "metropolis":
        raise ValueError(
            "the JAX annealer supports only moves=(0, 1, 2) with "
            f"acceptance='metropolis'; got moves={params.moves!r}, "
            f"acceptance={params.acceptance!r} — use the Python backend "
            "for ablation configs")
    cfg = JaxSAConfig(T0=params.T0, T_thres=params.T_thres, iters=1,
                      tau=params.tau, num_chains=num_chains)
    if params.budget_mode == "global":
        iters = max(1, -(-params.iters // cfg.n_levels))      # ceil div
    else:
        iters = params.iters
    return dataclasses.replace(cfg, iters=iters)


# --------------------------------------------------------------- packing
def _pad_len(n: int) -> int:
    """Bucket N to the next power of two (>= 8) so online re-annealing at
    shifting queue depths reuses a handful of jit compilations instead of
    one per depth."""
    return max(8, 1 << max(int(n) - 1, 0).bit_length())


def _pack(arrays_np: dict, model, pad_to: int) -> jnp.ndarray:
    """Build the padded per-request coefficient matrix [pad_to, 11].

    Built in float64 and converted by ``jnp.asarray`` so the dtype follows
    the x64 flag (f32 by default, f64 under ``jax.enable_x64(True)``
    for oracle-parity tests).  Padding rows are all-zero with VALID = 0:
    zero exec/prefill coefficients keep them out of batch durations and
    the latency sum, and the mask keeps them out of the met count.
    """
    n = len(arrays_np["input_len"])
    coefs = linear_request_coefs(arrays_np, model)
    cols = np.zeros((pad_to, _NCOLS), np.float64)
    for c, k in ((_EA, "eA"), (_EC, "eC"), (_PA, "pA"), (_PC, "pC"),
                 (_TA, "tA"), (_TC, "tC")):
        cols[:n, c] = coefs[k]
    cols[:n, _H] = np.asarray(arrays_np["h"], np.float64)
    cols[:n, _SE] = np.asarray(arrays_np["slo_e2e"], np.float64)
    cols[:n, _ST] = np.asarray(arrays_np["slo_ttft"], np.float64)
    cols[:n, _SP] = np.asarray(arrays_np["slo_tpot"], np.float64)
    cols[:n, _VALID] = 1.0
    return jnp.asarray(cols)


# ------------------------------------------------------------- objective
def _eval_g(reqc, perm, bnd):
    """Full Eq. 2 objective with segment ops over all N positions — the
    in-jit oracle the incremental path is checked against.  Returns
    ``(G, n_met)``; padding (VALID = 0) is excluded from both the met
    count and the latency denominator."""
    n = perm.shape[0]
    r = reqc[perm]
    batch_id = jnp.cumsum(bnd.astype(jnp.int32)) - 1          # [N]
    bsz = jnp.bincount(batch_id, length=n).astype(r.dtype)
    b_of = bsz[batch_id]

    t_exec = r[:, _EA] * b_of + r[:, _EC]
    t_pref = r[:, _PA] * b_of + r[:, _PC]
    t_tpot = r[:, _TA] * b_of + r[:, _TC]

    bdur = jax.ops.segment_max(t_exec, batch_id, num_segments=n)
    bdur = jnp.where(bsz > 0, bdur, 0.0)
    wait_b = jnp.concatenate([jnp.zeros((1,), bdur.dtype),
                              jnp.cumsum(bdur)[:-1]])
    t_wait = wait_b[batch_id]
    e2e = t_exec + t_wait
    ttft = t_pref + t_wait
    met = jnp.where(r[:, _H] == 1, e2e <= r[:, _SE],
                    (ttft <= r[:, _ST]) & (t_tpot <= r[:, _SP]))
    valid = r[:, _VALID] > 0
    n_met = jnp.sum((met & valid).astype(r.dtype))
    total = jnp.sum(jnp.where(valid, e2e, 0.0))
    return n_met / jnp.maximum(total, 1e-12), n_met


# ----------------------------------------------- incremental batch stats
# ``stats`` is a 5-tuple of arrays, row p describing the batch *starting
# at position p* (neutral everywhere else):
#   slacks [N, mb] — member SLO slacks sorted ascending, +inf padding
#   bsz    [N]     — structural batch size (incl. padding members)
#   cnt    [N]     — valid-member count (met/latency accounting)
#   sume   [N]     — sum of member exec times
#   bdur   [N]     — batch duration (max member exec)
def _row(reqc, perm_pad, start, size, mb: int):
    """Segment aggregates for a batch of ``size`` members at positions
    ``start .. start+size-1``.  ``perm_pad`` is perm padded with mb
    sentinels so the fixed-size window never clamps.  A member's *slack*
    is the largest batch wait under which it still meets its SLO:

      h = 1:  slack = slo_e2e  - exec(size)
      h = 0:  slack = slo_ttft - prefill(size)   if TPOT ok at this size,
              else -inf (can never be met)

    Non-members and padding get +inf (sorted last, never counted met).
    ``size == 0`` yields the neutral row."""
    idx = jax.lax.dynamic_slice(perm_pad, (start,), (mb,))
    r = reqc[idx]                                             # [mb, 11]
    memb = jnp.arange(mb) < size
    b = size.astype(r.dtype)
    ex = jnp.where(memb, r[:, _EA] * b + r[:, _EC], 0.0)
    sum_exec = jnp.sum(ex)
    bdur = jnp.where(size > 0,
                     jnp.max(jnp.where(memb, ex, -jnp.inf)), 0.0)
    pref = r[:, _PA] * b + r[:, _PC]
    tpot_ok = r[:, _TA] * b + r[:, _TC] <= r[:, _SP]
    slack = jnp.where(r[:, _H] == 1, r[:, _SE] - ex,
                      jnp.where(tpot_ok, r[:, _ST] - pref, -jnp.inf))
    live = memb & (r[:, _VALID] > 0)
    slack = jnp.where(live, slack, jnp.inf)
    cnt = jnp.sum(live.astype(r.dtype))
    return jnp.sort(slack), b, cnt, sum_exec, bdur


def _build_stats(reqc, perm, bnd, mb: int):
    """Vectorized O(N·mb) stats build for a whole schedule (used once per
    start; the anneal hot loop only rebuilds touched rows)."""
    n = perm.shape[0]
    pos = jnp.arange(n)
    batch_id = jnp.cumsum(bnd.astype(jnp.int32)) - 1
    sizes = jnp.bincount(batch_id, length=n)[batch_id]        # [N]
    perm_pad = jnp.concatenate([perm, jnp.zeros((mb,), perm.dtype)])
    slacks, bsz, cnt, sume, bdur = jax.vmap(
        lambda p, s: _row(reqc, perm_pad, p, s, mb))(pos, sizes)
    z = jnp.zeros((), reqc.dtype)
    return (jnp.where(bnd[:, None], slacks, jnp.inf),
            jnp.where(bnd, bsz, z), jnp.where(bnd, cnt, z),
            jnp.where(bnd, sume, z), jnp.where(bnd, bdur, z))


def _count_below(slack_rows, w):
    """Per-row count of slacks strictly below the row's wait — a batched
    ``searchsorted(row, w, side="left")`` into the sorted slack segments.
    For the mb-wide rows a masked compare-reduce computes the same rank
    in one fused kernel, which beats a vmapped binary search on CPU; the
    sorted order still matters (it is what makes the count a rank and
    keeps the Python/JAX backends' data structures interchangeable)."""
    return jnp.sum(slack_rows < w[..., None], axis=-1)


def _wait_prefix(bdur):
    """Exclusive prefix sums of batch durations — batch waits (Eq. 11)."""
    return jnp.concatenate([jnp.zeros((1,), bdur.dtype),
                            jnp.cumsum(bdur)[:-1]])


def _agg(stats, mb: int):
    """Score a schedule from its batch-stat rows alone:
    O(n_batches · log max_batch), no N-wide gathers."""
    slacks, bsz, cnt, sume, bdur = stats
    w = _wait_prefix(bdur)
    below = _count_below(slacks, w)
    n_met = jnp.sum(cnt - below.astype(cnt.dtype))
    total = jnp.sum(sume) + jnp.dot(cnt, w)
    return n_met / jnp.maximum(total, 1e-12), n_met


def _agg_delta(stats, sidx, rows, mb: int):
    """Score a candidate whose only changes vs the committed ``stats``
    are the 3 rebuilt rows ``rows`` at ``sidx`` — without materializing
    the candidate.  The wait prefix cache and the met/latency sums are
    recomputed over the [N] per-batch arrays with the touched entries
    overridden; untouched batches keep their sorted slack segments and
    only see a shifted wait."""
    slacks, bsz, cnt, sume, bdur = stats
    r_sl, r_b, r_cnt, r_se, r_bd = rows
    bdur_c = bdur.at[sidx].set(r_bd)
    cnt_c = cnt.at[sidx].set(r_cnt)
    sume_c = sume.at[sidx].set(r_se)
    w = _wait_prefix(bdur_c)
    below = _count_below(slacks, w).at[sidx].set(_count_below(r_sl, w[sidx]))
    n_met = jnp.sum(cnt_c - below.astype(cnt_c.dtype))
    total = jnp.sum(sume_c) + jnp.dot(cnt_c, w)
    return n_met / jnp.maximum(total, 1e-12), n_met


# ----------------------------------------------------------------- moves
def _sample_move(key, n_valid):
    """One (op, i, j) proposal plus the acceptance uniform, from a single
    4-draw so PRNG traffic stays off the hot path.  The same stream
    drives both scoring paths."""
    key, sub = jax.random.split(key)
    u = jax.random.uniform(sub, (4,))
    op = jnp.minimum((u[0] * 3).astype(jnp.int32), 2)
    hi = jnp.maximum(n_valid, 2)
    i = jnp.minimum(1 + (u[1] * (hi - 1).astype(u.dtype)).astype(jnp.int32),
                    hi - 1)
    lo_n = jnp.maximum(n_valid, 1)
    j = jnp.minimum((u[2] * lo_n.astype(u.dtype)).astype(jnp.int32),
                    lo_n - 1)
    return key, op, i, j, u[3]


def _start_of(bnd, i):
    """Start position of the batch containing position ``i``
    (bnd[0] is invariantly True, so the result is always >= 0)."""
    pos = jnp.arange(bnd.shape[0])
    return jnp.max(jnp.where(bnd & (pos <= i), pos, -1))


def _move_descriptors(perm, bnd, op, i, j, n_valid, mb: int):
    """Branch-free squeeze/delay/swap descriptor arithmetic shared by
    BOTH scoring paths, so their feasible move sets cannot diverge:
    the validity flag, the <= 2 perm entries a swap touches, and the
    <= 2 boundary bits a squeeze/delay touches (no-op writes of
    position 0 / the invariant bnd[0]=True otherwise).  Returns
    ``(ok, a_im1, i2, pidx, pval, bidx, bval)``."""
    n = perm.shape[0]
    is_sq = op == 0
    is_dl = op == 1
    is_sw = op == 2
    a_im1 = _start_of(bnd, i - 1)          # start of batch holding i-1
    i2 = jnp.minimum(i + 1, n - 1)
    # squeeze grows the previous batch (size i - a_im1 when bnd[i]) by
    # one; delay splits (never grows); swap only needs j in range
    ok = (i < n_valid) & jnp.where(
        is_sq, bnd[i] & (i - a_im1 < mb),
        jnp.where(is_dl, ~bnd[i], j < n_valid))
    z = jnp.zeros_like(i)
    pi, pj = perm[i], perm[j]
    pidx = jnp.where(is_sw, jnp.stack([i, j]), jnp.stack([z, z]))
    pval = jnp.where(is_sw, jnp.stack([pj, pi]),
                     jnp.stack([perm[0], perm[0]]))
    t_ = jnp.ones((), bool)
    bidx = jnp.where(is_sq, jnp.stack([i, i2]),
                     jnp.where(is_dl, jnp.stack([i, i]),
                               jnp.stack([z, z])))
    bval = jnp.where(is_sq, jnp.stack([jnp.zeros((), bool), i + 1 < n]),
                     jnp.stack([t_, t_]))
    return ok, a_im1, i2, pidx, pval, bidx, bval


def _candidate(reqc, perm, bnd, stats, op, i, j, n_valid, mb: int):
    """Move ``(op, i, j)`` as a branch-free sparse update.

    Every move is "rebuild <= 3 batch rows + <= 2 boundary bits +
    <= 2 perm entries", so instead of a ``lax.switch`` the descriptors
    (row start positions and new sizes) are selected arithmetically and
    all three rows are rebuilt by ONE vmapped :func:`_row` — far fewer
    ops inside the jitted loop.  Returns ``(ok, perm_c, upd)`` where
    ``perm_c`` is the candidate permutation (needed to build the rows)
    and ``upd = (pidx, pval, bidx, bval, sidx, rows)`` are the sparse
    updates; ``ok=False`` candidates carry garbage rows and must not be
    committed (:func:`_apply` with ``accept=False`` is a no-op)."""
    _, bsz, _, _, _ = stats
    is_sq = op == 0
    is_dl = op == 1
    ok, a_im1, i2, pidx, pval, bidx, bval = _move_descriptors(
        perm, bnd, op, i, j, n_valid, mb)
    a_i = jnp.where(bnd[i], i, a_im1)      # start of batch holding i
    a_j = _start_of(bnd, j)
    s_prev = bsz[a_im1].astype(jnp.int32)
    s_cur = bsz[i].astype(jnp.int32)
    s_old = bsz[a_i].astype(jnp.int32)
    s_j = bsz[a_j].astype(jnp.int32)
    left = i - a_i

    # squeeze: the batch starting at i loses its first member to the
    # previous batch; survivors re-start at i+1.  Rebuilding the (i+1)
    # row with its *current* size is a no-op when the squeezed batch was
    # a singleton followed by another batch, and yields the neutral row
    # (size 0) when i was the last position.
    sq3 = jnp.where(s_cur > 1, s_cur - 1,
                    jnp.where(i2 == i, 0, bsz[i2].astype(jnp.int32)))
    starts = jnp.where(
        is_sq, jnp.stack([a_im1, i, i2]),
        jnp.where(is_dl, jnp.stack([a_i, i, i]),
                  jnp.stack([a_i, a_j, a_j])))
    sizes = jnp.where(
        is_sq, jnp.stack([s_prev + 1, 0, sq3]),
        jnp.where(is_dl, jnp.stack([left, s_old - left, s_old - left]),
                  jnp.stack([s_old, s_j, s_j])))

    perm_c = perm.at[pidx].set(pval)
    perm_pad = jnp.concatenate([perm_c, jnp.zeros((mb,), perm.dtype)])
    rows = jax.vmap(lambda s, sz: _row(reqc, perm_pad, s, sz, mb))(
        starts, sizes)
    return ok, perm_c, (pidx, pval, bidx, bval, starts, rows)


def _apply(perm, bnd, stats, upd, accept):
    """Commit (``accept=True``) or discard a candidate's sparse updates —
    scatters only, never an O(N) select.  Duplicate indices in an update
    always carry identical values, so scatter order is immaterial."""
    pidx, pval, bidx, bval, sidx, rows = upd
    slacks, bsz, cnt, sume, bdur = stats
    r_sl, r_b, r_cnt, r_se, r_bd = rows
    sel = lambda new, cur: jnp.where(accept, new, cur)  # noqa: E731
    perm = perm.at[pidx].set(sel(pval, perm[pidx]))
    bnd = bnd.at[bidx].set(sel(bval, bnd[bidx]))
    stats = (slacks.at[sidx].set(sel(r_sl, slacks[sidx])),
             bsz.at[sidx].set(sel(r_b, bsz[sidx])),
             cnt.at[sidx].set(sel(r_cnt, cnt[sidx])),
             sume.at[sidx].set(sel(r_se, sume[sidx])),
             bdur.at[sidx].set(sel(r_bd, bdur[sidx])))
    return perm, bnd, stats


def _structural(perm, bnd, op, i, j, n_valid, mb: int):
    """Move application for the full-evaluate path (no stats carried) —
    the same :func:`_move_descriptors` arithmetic as the incremental
    path, applied densely, so both paths see one feasible move set by
    construction."""
    ok, _, _, pidx, pval, bidx, bval = _move_descriptors(
        perm, bnd, op, i, j, n_valid, mb)
    return ok, perm.at[pidx].set(pval), bnd.at[bidx].set(bval)


# ----------------------------------------------------------------- chains
def _starts(reqc, n_valid, mb: int):
    """The two Algorithm 1 starting solutions under padding: predicted-e2e
    order and arrival order, maximal batches over the real prefix, padding
    pinned as tail singletons."""
    n = reqc.shape[0]
    pos = jnp.arange(n)
    t0 = reqc[:, _EA] * mb + reqc[:, _EC]
    t0 = jnp.where(reqc[:, _VALID] > 0, t0, jnp.inf)
    perm_s = jnp.argsort(t0).astype(jnp.int32)                # stable
    perm_a = pos.astype(jnp.int32)
    bnd0 = ((pos % mb) == 0) | (pos >= n_valid)
    return perm_s, perm_a, bnd0


def anneal_chain(key, reqc, n_valid, max_batch: int, cfg: JaxSAConfig,
                 incremental: bool = True):
    """One SA chain over the padded instance.  Returns
    ``(best_perm, best_bnd, best_G)``.  Mirrors Algorithm 1 including the
    line-7 early exit: the temperature loop stops as soon as the best
    solution seen meets every (valid) SLO."""
    mb = max_batch
    f_dtype = reqc.dtype
    perm_s, perm_a, bnd0 = _starts(reqc, n_valid, mb)
    if incremental:
        stats_s = _build_stats(reqc, perm_s, bnd0, mb)
        stats_a = _build_stats(reqc, perm_a, bnd0, mb)
        f_s, met_s = _agg(stats_s, mb)
        f_a, met_a = _agg(stats_a, mb)
    else:
        f_s, met_s = _eval_g(reqc, perm_s, bnd0)
        f_a, met_a = _eval_g(reqc, perm_a, bnd0)
    pick = f_s >= f_a
    perm = jnp.where(pick, perm_s, perm_a)
    f = jnp.where(pick, f_s, f_a)
    met = jnp.where(pick, met_s, met_a)
    if incremental:
        stats = jax.tree_util.tree_map(
            lambda a, b: jnp.where(pick, a, b), stats_s, stats_a)
    else:
        stats = ()
    f_ref = jnp.maximum(f, 1e-12)
    n_valid_f = n_valid.astype(f_dtype)

    def temp_cond(state):
        T, *_, bmet = state
        return (T >= cfg.T_thres) & (bmet < n_valid_f)

    def temp_body(state):
        T = state[0]

        def it_body(_, inner):
            key, perm, bnd, stats, f, met, bp, bb, bf, bmet = inner
            key, op, i, j, u_acc = _sample_move(key, n_valid)
            if incremental:
                ok, perm_c, upd = _candidate(reqc, perm, bnd, stats, op,
                                             i, j, n_valid, mb)
                f_new, met_new = _agg_delta(stats, upd[4], upd[5], mb)
            else:
                ok, perm_c, bnd_c = _structural(perm, bnd, op, i, j,
                                                n_valid, mb)
                f_new, met_new = _eval_g(reqc, perm_c, bnd_c)
            p_acc = jnp.exp((f_new - f) / (f_ref * T / cfg.T0))
            accept = ok & ((f_new > f) | (u_acc < p_acc))
            if incremental:
                perm, bnd, stats = _apply(perm, bnd, stats, upd, accept)
            else:
                perm = jnp.where(accept, perm_c, perm)
                bnd = jnp.where(accept, bnd_c, bnd)
            f = jnp.where(accept, f_new, f)
            met = jnp.where(accept, met_new, met)
            better = f > bf
            bp = jnp.where(better, perm, bp)
            bb = jnp.where(better, bnd, bb)
            bf = jnp.where(better, f, bf)
            bmet = jnp.where(better, met, bmet)
            return key, perm, bnd, stats, f, met, bp, bb, bf, bmet

        inner = jax.lax.fori_loop(0, cfg.iters, it_body, state[1:])
        return (T * cfg.tau,) + inner

    T0 = jnp.asarray(cfg.T0, f_dtype)
    state = (T0, key, perm, bnd0, stats, f, met, perm, bnd0, f, met)
    state = jax.lax.while_loop(temp_cond, temp_body, state)
    _, _, _, _, _, _, _, best_perm, best_bnd, best_f, _ = state
    return best_perm, best_bnd, best_f


@partial(jax.jit, static_argnames=("max_batch", "cfg", "incremental"))
def _run_chains(keys, reqc, n_valid, max_batch: int, cfg: JaxSAConfig,
                incremental: bool):
    return jax.vmap(
        lambda k: anneal_chain(k, reqc, n_valid, max_batch, cfg,
                               incremental))(keys)


@partial(jax.jit, static_argnames=("max_batch", "cfg", "incremental"))
def _run_chains_multi(keys, reqcs, n_valids, max_batch: int,
                      cfg: JaxSAConfig, incremental: bool):
    """instances × chains in one jitted program: the outer vmap batches
    Algorithm 2's per-instance mapping, the inner one the tempering
    chains."""
    return jax.vmap(
        lambda ks, rc, nv: jax.vmap(
            lambda k: anneal_chain(k, rc, nv, max_batch, cfg,
                                   incremental))(ks))(keys, reqcs, n_valids)


# -------------------------------------------------------------- frontends
def _validate(max_batch: int, cfg: JaxSAConfig):
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    if not isinstance(cfg, JaxSAConfig):
        raise TypeError(f"cfg must be a JaxSAConfig, got {type(cfg)}")


def _extract(perm_pad, bnd_pad, n: int):
    perm = np.asarray(perm_pad)[:n]
    bnd = np.asarray(bnd_pad)[:n]
    batch_id = np.cumsum(bnd.astype(np.int64)) - 1
    return perm.astype(np.int64), batch_id


def priority_mapping_jax(arrays_np: dict, model, max_batch: int,
                         cfg: Optional[JaxSAConfig] = None, seed: int = 0,
                         incremental: bool = True):
    """vmapped parallel-tempering front end.  Returns
    ``(perm, batch_id, G)`` for the best chain.

    ``incremental=True`` (default) scores proposals with the jitted
    incremental-Δ evaluator; ``incremental=False`` re-evaluates the full
    objective per proposal (the oracle path, kept for cross-checking and
    benchmarking — see docs/annealer.md).
    """
    cfg = JaxSAConfig() if cfg is None else cfg
    _validate(max_batch, cfg)
    n = len(arrays_np["input_len"])
    if n == 0:
        return (np.zeros(0, np.int64), np.zeros(0, np.int64), 0.0)
    reqc = _pack(arrays_np, model, _pad_len(n))
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.num_chains)
    perms, bnds, fs = _run_chains(keys, reqc, jnp.int32(n), max_batch,
                                  cfg, incremental)
    best = int(jnp.argmax(fs))
    perm, batch_id = _extract(perms[best], bnds[best], n)
    return perm, batch_id, float(fs[best])


def priority_mapping_multi_jax(arrays_list: Sequence[dict], model,
                               max_batch: int,
                               cfg: Optional[JaxSAConfig] = None,
                               seed: int = 0, incremental: bool = True
                               ) -> List[Tuple[np.ndarray, np.ndarray,
                                               float]]:
    """Batch Algorithm 2's per-instance priority mapping as ONE jitted
    program: instances × chains, ragged instance loads padded to a common
    power-of-two length and masked out of the objective.

    ``arrays_list`` holds one columnar request view (``slo.as_arrays``)
    per instance; returns a ``(perm, batch_id, G)`` triple per instance,
    trimmed back to its real length.  Instance ``i`` anneals with PRNG
    key ``fold_in(PRNGKey(seed), i)`` so fleets are reproducible and
    instances stay independent.
    """
    cfg = JaxSAConfig() if cfg is None else cfg
    _validate(max_batch, cfg)
    sizes = [len(a["input_len"]) for a in arrays_list]
    if not sizes:
        return []
    pad = _pad_len(max(max(sizes), 1))
    reqcs = jnp.stack([_pack(a, model, pad) for a in arrays_list])
    n_valids = jnp.asarray(sizes, jnp.int32)
    base = jax.random.PRNGKey(seed)
    keys = jnp.stack([
        jax.random.split(jax.random.fold_in(base, i), cfg.num_chains)
        for i in range(len(sizes))])
    perms, bnds, fs = _run_chains_multi(keys, reqcs, n_valids, max_batch,
                                        cfg, incremental)
    out = []
    for i, n in enumerate(sizes):
        if n == 0:
            out.append((np.zeros(0, np.int64), np.zeros(0, np.int64), 0.0))
            continue
        best = int(jnp.argmax(fs[i]))
        perm, batch_id = _extract(perms[i, best], bnds[i, best], n)
        out.append((perm, batch_id, float(fs[i, best])))
    return out
