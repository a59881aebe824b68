"""Open-loop traffic from a mix file, and the window arithmetic.

The generator is a copy of the program's trace replay
(``repro.data.traces``: inverse-CDF sampling of checked-in length
histograms, seeded arrival processes), kept here so that no change to
the program can change the yardstick.

One difference, for steadiness: a mix file's ``base_seed`` fixes the
requests due in the ramp and, apart, in the window (classes, sizes and
arrival times), and ``--seed`` draws only the prompt tokens.  Every seed
offers the same work, so the spread between runs is the system's and not
the draw's.  Not even the order is the seed's: requests outlast a good
part of the window, so the order alone moves the tokens delivered in it.

A mix file (``traffic/<name>.json``)::

    {"profiles": [...], "mix": [...], "base_seed": 7,
     "arrival": {"process": "poisson", "rate": 8.0},
     "ramp_s": 10, "drain_cap_s": 30, "slo_scale": 1.0,
     "policy": "slo-reanneal", "discipline": "stall"}

Arrivals cover ``ramp_s + seconds``.  Requests due in the ramp fill the
batch and are not counted; requests due in the window are what every
end-to-end metric is over.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import numpy as np

TRACES = Path(__file__).resolve().parent / "traces"


# ------------------------------------------------------------ histograms
@dataclasses.dataclass(frozen=True)
class Histogram:
    """``k+1`` ascending edges and ``k`` masses; inverse-CDF sampling."""
    edges: tuple
    counts: tuple

    def sample(self, rng: np.random.Generator, n: int) -> np.ndarray:
        p = np.asarray(self.counts, np.float64)
        p = p / p.sum()
        bins = rng.choice(len(p), size=n, p=p)
        lo = np.asarray(self.edges[:-1], np.float64)[bins]
        hi = np.asarray(self.edges[1:], np.float64)[bins]
        vals = lo + rng.random(n) * (hi - lo)
        return np.maximum(vals.astype(np.int64), 1)


@dataclasses.dataclass(frozen=True)
class Profile:
    name: str
    task_type: str
    slo: Dict[str, Optional[float]]     # e2e / ttft / tpot, seconds
    input: Histogram
    output: Histogram


def load_profile(name: str, traces: Path = TRACES) -> Profile:
    with open(traces / f"{name}.json") as f:
        obj = json.load(f)
    slo = obj.get("slo") or {}
    return Profile(
        name=obj["name"], task_type=obj.get("task_type", obj["name"]),
        slo={k: slo.get(k) for k in ("e2e", "ttft", "tpot")},
        input=Histogram(tuple(obj["input"]["edges"]),
                        tuple(obj["input"]["counts"])),
        output=Histogram(tuple(obj["output"]["edges"]),
                         tuple(obj["output"]["counts"])))


# -------------------------------------------------------------- arrivals
def poisson_gaps(n: int, rate: float, rng, **_) -> np.ndarray:
    return rng.exponential(1.0 / rate, n)


def bursty_gaps(n: int, rate: float, rng, burst: float = 8.0,
                burst_frac: float = 0.15, dwell: int = 16) -> np.ndarray:
    """2-state MMPP at mean ``rate`` (``repro.data.traces``)."""
    r_calm = rate * ((1.0 - burst_frac) + burst_frac / burst)
    rates = (r_calm, r_calm * burst)
    dwells = (max(dwell * (1 - burst_frac) / max(burst_frac, 1e-9), 1.0),
              float(max(dwell, 1)))
    state = 1 if rng.random() < burst_frac else 0
    gaps = np.empty(n)
    for i in range(n):
        gaps[i] = rng.exponential(1.0 / rates[state])
        if rng.random() < 1.0 / dwells[state]:
            state = 1 - state
    return gaps


GAPS = {"poisson": poisson_gaps, "bursty": bursty_gaps}


# ----------------------------------------------------------------- mixes
@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    profiles: tuple
    weights: tuple
    base_seed: int
    process: str
    rate: float
    arrival_kw: dict
    ramp_s: float
    drain_cap_s: float
    slo_scale: float
    policy: str
    discipline: str


def load_mix(path: Path) -> Mix:
    with open(path) as f:
        m = json.load(f)
    arr = dict(m["arrival"])
    process, rate = arr.pop("process"), float(arr.pop("rate"))
    if process not in GAPS:
        raise ValueError(f"unknown arrival process {process!r}")
    return Mix(name=path.stem, profiles=tuple(m["profiles"]),
               weights=tuple(m["mix"]), base_seed=int(m["base_seed"]),
               process=process, rate=rate, arrival_kw=arr,
               ramp_s=float(m["ramp_s"]),
               drain_cap_s=float(m["drain_cap_s"]),
               slo_scale=float(m["slo_scale"]), policy=m["policy"],
               discipline=m["discipline"])


@dataclasses.dataclass
class Req:
    """One request of a run: when it is due (seconds from the start of
    the ramp), its sizes, its class's SLO and its prompt."""
    idx: int
    task_type: str
    due: float
    input_len: int
    output_len: int
    slo: Dict[str, Optional[float]]
    prompt: np.ndarray


def generate(mix: Mix, seed: int, seconds: float, vocab: int,
             max_total: int, traces: Path = TRACES,
             rate: Optional[float] = None) -> List[Req]:
    """The run's requests, sorted by due time.

    The base draw (from ``mix.base_seed``) fixes, for the ramp and for
    the window each, how many requests are due in it, when, and their
    classes and lengths; ``seed`` draws the prompt tokens.
    ``input + output`` is capped at ``max_total`` (the slot length) by
    trimming the output.  ``rate`` overrides the mix's rate (sweeps)."""
    rate = mix.rate if rate is None else rate
    base = np.random.default_rng(mix.base_seed)
    profs = [load_profile(p, traces) for p in mix.profiles]
    w = np.asarray(mix.weights, np.float64)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 1]))
    due, sizes = [], []
    for t0, span in ((0.0, mix.ramp_s), (mix.ramp_s, seconds)):
        n = max(1, int(math.ceil(span * rate * 1.5)) + 16)
        gaps = GAPS[mix.process](n, rate, base, **mix.arrival_kw)
        n = int(np.searchsorted(np.cumsum(gaps), span))
        which = base.choice(len(profs), size=n, p=w / w.sum())
        lin = np.stack([p.input.sample(base, n) for p in profs])
        lout = np.stack([p.output.sample(base, n) for p in profs])
        part = []
        for i in range(n):
            a = int(min(lin[which[i], i], max_total - 1))
            b = int(max(1, min(lout[which[i], i], max_total - a)))
            part.append((int(which[i]), a, b))
        due.extend(t0 + np.cumsum(gaps[:n]))
        sizes.extend(part)
    reqs = []
    for i in range(len(sizes)):
        k, a, b = sizes[i]
        slo = {key: (None if v is None else v * mix.slo_scale)
               for key, v in profs[k].slo.items()}
        reqs.append(Req(idx=i, task_type=profs[k].task_type,
                        due=float(due[i]), input_len=a, output_len=b,
                        slo=slo,
                        prompt=rng.integers(0, vocab, a).astype(np.int32)))
    return reqs


# ---------------------------------------------------------------- window
@dataclasses.dataclass
class Outcome:
    """What a request got: delivery times (seconds from the start of the
    ramp) of each token, and whether it was rejected or errored."""
    times: List[float]
    rejected: bool = False


def meets_slo(slo, e2e: float, ttft: float, tpot: float) -> bool:
    """Eq. 7 of the paper (``repro.core.slo.meets_slo``): an e2e class is
    judged on e2e alone, an interactive class on TTFT and TPOT."""
    if slo.get("e2e") is not None:
        return e2e <= slo["e2e"]
    ok = True
    if slo.get("ttft") is not None:
        ok &= ttft <= slo["ttft"]
    if slo.get("tpot") is not None:
        ok &= tpot <= slo["tpot"]
    return bool(ok)


def percentile(xs: Sequence[float], p: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), p))


def window_metrics(reqs: Sequence[Req], outcomes: Dict[int, Outcome],
                   ramp_s: float, seconds: float, t_end: float) -> dict:
    """End-to-end numbers over the window ``[ramp_s, ramp_s + seconds)``.

    * attempted: requests due in the window.  failed: those rejected,
      errored, or without their full output by ``t_end`` (the drain cap).
    * ttft_p95_ms: over every attempted request, from due to first
      token; one with no token counts ``t_end - due``, a lower bound.
    * tbt_p95_ms: every gap between consecutive tokens of one request
      with both deliveries inside the window, pooled.
    * output_tok_s: tokens delivered inside the window / its length.
    * slo_attainment: share of attempted requests that met their SLO
      (``meets_slo`` on e2e, TTFT and TPOT = (last - first) / tokens);
      a failed request missed.
    """
    w0, w1 = ramp_s, ramp_s + seconds
    due = [r for r in reqs if w0 <= r.due < w1]
    ttfts, met, failed = [], 0, 0
    for r in due:
        o = outcomes.get(r.idx)
        times = o.times if o is not None else []
        ok = (o is not None and not o.rejected
              and len(times) == r.output_len)
        ttfts.append((times[0] if times else t_end) - r.due)
        if not ok:
            failed += 1
            continue
        e2e, ttft = times[-1] - r.due, times[0] - r.due
        tpot = (times[-1] - times[0]) / max(len(times), 1)
        met += meets_slo(r.slo, e2e, ttft, tpot)
    gaps, tokens = [], 0
    for o in outcomes.values():
        inside = [t for t in o.times if w0 <= t < w1]
        tokens += len(inside)
        gaps.extend(b - a for a, b in zip(inside, inside[1:]))
    return {
        "attempted": len(due), "failed": failed,
        "ttft_p95_ms": percentile(ttfts, 95) * 1e3 if ttfts else None,
        "ttft_p50_ms": percentile(ttfts, 50) * 1e3 if ttfts else None,
        "tbt_p95_ms": percentile(gaps, 95) * 1e3 if gaps else None,
        "tbt_p50_ms": percentile(gaps, 50) * 1e3 if gaps else None,
        "n_gaps": len(gaps),
        "output_tok_s": tokens / seconds,
        "slo_attainment": 100.0 * met / len(due) if due else None,
    }
