"""Serving launcher: live streaming loop (default) or batch engine run
on a synthetic mixed workload.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --n 12 --policy slo-reanneal:jax --arrival-rate 20

``--policy`` accepts ANY ``repro.core.policies.make`` registry name
(``fcfs``, ``priority``, ``slo-reanneal``, ``slo-reanneal:jax``,
``slo-preempt``, …) plus ``slo``/``planned`` for the offline Algorithm
1/2 planner (plan batches, then dispatch).  Streaming mode drives the
:class:`repro.serving.ServeLoop` — arrival-timed ingestion, per-token
wall-clock streams, overlapped host scheduling + device execution — and
reports *measured* TTFT/TBT/attainment; ``--mode batch`` runs the
engine's batch admission loop on its internal clock instead (the
planner policies always use batch mode: their plan needs the whole
workload up front).  ``--discipline chunked:<n>`` and
``--policy dynamic-chunk`` stream natively: prefill chunks ride the
serving ticks alongside running decode dispatches (chunk-as-tick).
``--instances N`` scales streaming mode data-parallel: an
:class:`repro.serving.EngineFleet` of N engines routed by ``--mapper``
(least-loaded default; ``annealed`` runs the paper's Algorithm 2 as the
routing plan — see docs/sharding.md); replica *i* runs on
``jax.devices()[i]``, and when N exceeds the device count replicas share
devices round-robin (an arch with no attention layers keeps every
replica on the default device).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.configs import get_config, get_reduced
from repro.core import SAParams, SLOAwareScheduler
from repro.core.policies import make, make_discipline
from repro.core.profiler import LatencyProfiler
from repro.data.synthetic import sample_serve_workload
from repro.engine.engine import Engine
from repro.engine.request import RuntimeRequest
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import init_params
from repro.serving import EngineFleet, ServeLoop


def _to_rts(pairs):
    return [RuntimeRequest(request=r, prompt_tokens=p,
                           max_new_tokens=r.output_len)
            for r, p in pairs]


def fit_latency_model(cfg, params, max_batch, rng, n_warm=10):
    """Fit the linear latency model on a short profiled warmup run on
    this process's device.  Raises when the run gave the profiler too
    few samples to fit (rather than serving on another device's
    coefficients)."""
    prof = LatencyProfiler()
    warm = Engine(cfg, params, max_slots=max_batch, max_seq_len=256,
                  profiler=prof)
    warm.run_fcfs(_to_rts(sample_serve_workload(n_warm, cfg.vocab_size,
                                                rng=rng)))
    if not prof.ready:
        raise RuntimeError(
            f"latency fit needs more samples: {len(prof.prefill_samples)} "
            f"prefill, {len(prof.decode_samples)} decode from {n_warm} "
            "warm-up requests")
    # non-negative: the policies price schedules with it, and an
    # extrapolated negative step time would make slack grow with load
    return prof.fit(nonneg=True)


def make_fleet_engines(cfg, params, n: int, **engine_kw):
    """``n`` engines, replica ``i`` committed to ``jax.devices()[i]`` by
    a one-device mesh; past the device count, replicas share devices
    round-robin (``i % count``) — said out loud, since shared devices
    serialize.  An arch with no attention layers runs the dense engine,
    which takes no mesh: its replicas all share the default device."""
    devs = jax.devices()
    if not cfg.attn_layers:
        print(f"{n} replicas share the default device {devs[0].id}: "
              f"{cfg.name} has no attention layers, so no paged KV pool "
              "to place")
        return [Engine(cfg, params, **engine_kw) for _ in range(n)]
    if n > len(devs):
        print(f"{n} replicas share {len(devs)} device(s): replica i runs "
              f"on device i % {len(devs)}")
    return [Engine(cfg, params,
                   mesh=make_host_mesh(tp=1, devices=[devs[i % len(devs)]]),
                   **engine_kw) for i in range(n)]


def run_planner(eng, rts, model, discipline, max_batch, respect):
    """Offline Algorithm 1/2: plan batches, score, dispatch."""
    reqs = [rt.request for rt in rts]
    for rt, r in zip(rts, reqs):
        r.predicted_output_len = rt.max_new_tokens
    sched = SLOAwareScheduler(model, num_instances=1, max_batch=max_batch,
                              sa_params=SAParams(seed=0))
    outcome = sched.schedule(reqs)
    for disc in ("stall", f"chunked:{discipline.chunk_size or 32}"):
        ev = sched.evaluate_plan(outcome, discipline=disc)
        print(f"plan under {disc:<12}: predicted G={ev.G:.4f} "
              f"attainment={ev.attainment:.2f}")
    by_id = {rt.req_id: rt for rt in rts}
    planned = [[by_id[r.req_id] for r in b]
               for b in outcome.queues[0].batches]
    return eng.run_planned(planned, discipline=discipline, model=model,
                           respect_arrivals=respect)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--n", type=int, default=12)
    ap.add_argument("--policy", default="slo",
                    help="any policies.make registry name (fcfs, priority, "
                         "slo-reanneal[:jax], slo-preempt, ...) or "
                         "slo/planned for the offline planner")
    ap.add_argument("--mode", choices=("stream", "batch"), default="stream",
                    help="stream: live ServeLoop with measured wall-clock "
                         "metrics; batch: engine admission loop")
    ap.add_argument("--discipline", default="stall",
                    help="stall | chunked | chunked:<size> — both modes; "
                         "streaming runs chunks in the tick plan "
                         "alongside decode dispatches")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="requests/s; 0 = all submitted at t=0")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--no-overlap", action="store_true",
                    help="stream mode: synchronous reference loop")
    ap.add_argument("--instances", type=int, default=1,
                    help="stream mode: data-parallel EngineFleet size "
                         "(N engines behind one front door)")
    ap.add_argument("--mapper", default="least-loaded",
                    help="fleet routing: round-robin | least-loaded | "
                         "slo-affinity | memory-greedy | annealed")
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg, dtype="float32")
    if cfg.uses_extra_embeds:
        raise SystemExit("VLM serving needs an embedding frontend; use the "
                         "dry-run for qwen2-vl shapes")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    pairs = sample_serve_workload(args.n, cfg.vocab_size, rng=rng,
                                  arrival_rate=args.arrival_rate)
    discipline = make_discipline(args.discipline)
    model = fit_latency_model(cfg, params, args.max_batch, rng)

    planner = args.policy in ("slo", "planned")
    mode = args.mode
    fleet = mode == "stream" and not planner and args.instances > 1
    engines = make_fleet_engines(cfg, params, args.instances,
                                 max_slots=args.max_batch, max_seq_len=256) \
        if fleet else [Engine(cfg, params, max_slots=args.max_batch,
                              max_seq_len=256)]
    eng = engines[0]
    print(f"device={jax.devices()[0].device_kind} "
          f"kernel_mode={eng.kernel_mode}")
    if mode == "stream" and not planner:
        pol = make(args.policy, model=model, max_batch=args.max_batch)
        # a policy that carries its own discipline (dynamic-chunk) wins
        # over the flag — same convention as the batch path below.
        # Chunked disciplines stream natively (chunk-as-tick); only
        # MLA + chunked raises UnsupportedDisciplineError, which is a
        # real configuration error the user must fix.
        disc = getattr(pol, "discipline", None) or discipline
        if fleet:
            loop = EngineFleet(engines, args.policy, mapper=args.mapper,
                               model=model, discipline=disc,
                               overlap=not args.no_overlap)
        else:
            loop = ServeLoop(eng, pol, model=model, discipline=disc,
                             overlap=not args.no_overlap)
        loop.start(warm_lengths=[len(p) for _, p in pairs])
        loop.submit_trace(pairs)
        out = loop.serve()
        s = loop.metrics.summary()
        where = f"fleet{args.instances}:{args.mapper}" \
            if args.instances > 1 else "stream"
        print(f"policy={args.policy} mode={where} arch={cfg.name} "
              f"discipline={disc!r} overlap={not args.no_overlap} "
              f"G={s['G']:.4f} attainment={s['attainment']:.2f} "
              f"ttft_mean={s['ttft_mean'] * 1e3:.1f}ms "
              f"tbt_p90={s['tbt_p90'] * 1e3:.2f}ms "
              f"tok/s={s['tokens_per_s']:.0f} "
              f"preemptions={s['preemptions']} "
              f"queue_wait_p95={s['queue_wait_p95'] * 1e3:.1f}ms "
              f"warms={s.get('warms', 0)}")
        return
    rts = _to_rts(pairs)
    respect = args.arrival_rate > 0
    if planner:
        out = run_planner(eng, rts, model, discipline, args.max_batch,
                          respect)
    else:
        pol = make(args.policy, model=model, max_batch=args.max_batch)
        # a policy that carries its own discipline (dynamic-chunk) wins
        # over the flag — same convention as benchmarks/bench_goodput
        discipline = getattr(pol, "discipline", None) or discipline
        out = eng.run_policy(rts, pol, discipline=discipline, model=model,
                             respect_arrivals=respect)
    met = sum(v["met"] for v in out.values())
    tot = sum(v["e2e"] for v in out.values())
    npre = sum(v["preemptions"] for v in out.values())
    print(f"policy={args.policy} discipline={discipline!r} arch={cfg.name} "
          f"G={met / tot if tot else 0:.4f} attainment={met}/{len(out)} "
          f"avg={tot / len(out):.2f}s preemptions={npre}")


if __name__ == "__main__":
    main()
