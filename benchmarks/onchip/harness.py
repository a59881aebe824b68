"""One run of one cell: set up the served path, offer the cell's traffic
over a ramp and a measured window, drain, check the output against the
reference, and reduce what was seen to the cell's metrics.

The system under test is the path ``launch/serve.py --mode stream``
drives: a paged ``Engine`` behind a ``ServeLoop`` with the mix's policy
and discipline, the latency model fitted on this chip at start-up.  The
harness builds those objects and drives ``ServeLoop.tick``; it patches
no module.  With ``trace=1`` it wraps four methods of the instances it
built (``loop.tick``, the policy's ``decide``, ``eng.prefill_step``,
``eng.dispatch_decode``) in host spans and records each step's live
batch, and the window runs under the profiler.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import model, reference, traffic, work

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# the step programs' names in the device trace's ``XLA Modules`` line
DECODE_PROGRAM, PREFILL_PROGRAM = "decode_dispatch_paged", "prefill_paged"


# ------------------------------------------------------------- the cell
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    arch: ModuleType                    # archs/<arch>.py, as the config names
    spec: object                        # the arch module's spec
    mix: traffic.Mix
    check: dict                         # limits of the comparison
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path

    @property
    def bench_dir(self) -> Path:
        return self.root / "benchmarks" / "onchip"


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` and its files, found
    by the names the entry gives: the configuration and the architecture
    module it names, and the traffic mix."""
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    w = cells[name]
    d = root / "benchmarks" / "onchip"
    config, arch = model.load_config(d / "configs" / f"{w['config']}.json",
                                     d / "archs")

    def mine(m):
        return name in m.get("workloads", [name])
    return Cell(name=name, chips=int(w["chips"]), arch=arch,
                spec=arch.load_spec(config),
                mix=traffic.load_mix(d / "traffic" / f"{w['traffic']}.json"),
                check=config.get("check", {}),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                root=root)


def require_chips(n: int):
    """The TPU devices, or SystemExit when JAX finds none or fewer than
    the cell asks for (no result line is printed)."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"no TPU found (JAX platform {devs[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        raise SystemExit(3)
    if len(devs) < n:
        print(f"the cell needs {n} TPU chips, JAX found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(3)
    return devs[:n]


def setup_compile_cache(root: Path) -> str:
    """JAX's persistent cache at a fixed path: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` in the checkout.  Every program is
    kept, so a later run of the cell compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """XLA backend compiles (count, seconds) and persistent-cache hits in
    this process (as ``chip_smoke.CompileClock``)."""

    def __init__(self):
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.seconds, self.count, self.cache_hits = 0.0, 0, 0
        self._event = BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._hit)

    def _dur(self, event, secs, **_):
        if event == self._event:
            self.seconds += secs
            self.count += 1

    def _hit(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.count, self.cache_hits, self.seconds)


# ------------------------------------------------------------ recording
@dataclasses.dataclass
class Record:
    """What the traced run gathers for the per-layer readers."""
    arch: ModuleType                     # the cell's, for its work counts
    spec: object
    peak: dict
    gauges: list = dataclasses.field(default_factory=list)
    decide_s: List[float] = dataclasses.field(default_factory=list)
    decode_ctx: List[List[int]] = dataclasses.field(default_factory=list)
    prefill_n: List[int] = dataclasses.field(default_factory=list)
    trace: object = None                 # trace_reduce.Trace
    window_ns: tuple = (0, 0)            # the part the trace holds
    device: Optional[str] = None         # device plane read
    on: bool = False                     # inside the traced window


def _instrument(loop, eng, rec: Record):
    """Host spans and step records around the calls into each layer,
    on these instances only."""
    from jax.profiler import TraceAnnotation

    def wrap(obj, name, span, before=None, seconds=None):
        inner = getattr(obj, name)

        def call(*a, **k):
            if before is not None and rec.on:
                before(*a, **k)
            t = time.perf_counter()
            with TraceAnnotation(span):
                out = inner(*a, **k)
            if seconds is not None and rec.on:
                seconds.append(time.perf_counter() - t)
            return out
        setattr(obj, name, call)

    def on_prefill(rt, length=None):
        ctx = rt.input_len + len(rt.generated)
        left = ctx - (rt.prefill_pos or rt.cached_tokens)
        rec.prefill_n.append(left if length is None
                             else min(int(length), left))

    def on_dispatch(feed, active_np, width=None, lookahead=0):
        # tokens in cache after this step's write, as the loop counts
        # them (one round may still be in flight)
        rec.decode_ctx.append(
            [rt.input_len + len(rt.generated) + loop._inflight_count(rt)
             for s, rt in enumerate(eng.slot_req)
             if s < len(active_np) and active_np[s]])

    wrap(loop, "tick", "bench.tick")
    wrap(loop.pol, "decide", "bench.decide", seconds=rec.decide_s)
    wrap(eng, "prefill_step", "bench.prefill", on_prefill)
    wrap(eng, "dispatch_decode", "bench.dispatch", on_dispatch)


# --------------------------------------------------------------- set-up
def _buckets(lo: int, hi: int) -> List[int]:
    out, b = [], lo
    while True:
        out.append(b)
        if b >= hi:
            return out
        b *= 2


def prewarm(eng, max_prompt: int):
    """Compile (or load) every step program the cell's traffic can use:
    each pow-2 decode width and each prefill bucket up to the longest
    prompt.  Runs them once on the engine's own, still empty pool (the
    block tables point at the null page), so no copy of the pool is
    made, and marks them warm."""
    feed = jnp.zeros((eng.max_slots, 1), jnp.int32)
    for w in _buckets(1, eng.max_slots):
        w = min(w, eng.max_slots)
        toks, eng.cache = eng._dispatch_fn(
            eng.params, eng.cache, feed[:w], jnp.zeros(w, bool),
            jax.random.PRNGKey(0))
        jax.block_until_ready(toks)
        eng._warm.add(("dispatch", w))
    for L in _buckets(16, max_prompt):
        if L >= eng.max_seq_len:
            break
        logits, eng.cache = eng._prefill_fn(
            eng.params, eng.cache, jnp.zeros((1, L), jnp.int32), L, 0)
        jax.block_until_ready(logits)
        eng._warm.add(("prefill", L))
    eng.cache["pos"] = jnp.zeros_like(eng.cache["pos"])


def warm_serve(loop, vocab: int, max_slots: int, max_prompt: int,
               rng: np.random.Generator):
    """Serve a few short bursts through the loop itself, so that the
    small eager programs of the served path (block-table and feed
    scatters at every width, prefill sampling) exist before the window:
    a burst of ``w`` requests for each decode width ``w``, then one
    request per prefill bucket."""
    def burst(lengths, out):
        for n in lengths:
            loop.submit(rng.integers(0, vocab, n).astype(np.int32),
                        max_new_tokens=out)
        loop.serve()
    for w in _buckets(1, max_slots):
        burst([8] * min(w, max_slots), 3)
    burst([min(L, max_prompt) - 1 for L in _buckets(16, max_prompt)
           if min(L, max_prompt) > 1], 2)


# -------------------------------------------------------------- the run
def _slo(d):
    from repro.core.slo import SLO
    return SLO(e2e=d["e2e"], ttft=d["ttft"], tpot=d["tpot"])


def _load_readers(cell: Cell) -> Dict[str, object]:
    out = {}
    for m in cell.per_layer:
        path = cell.bench_dir / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"onchip_metric_{len(out)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out[m["name"]] = mod
    return out


@dataclasses.dataclass
class Server:
    """The served path as one run sets it up."""
    params: object
    eng: object
    loop: object
    clock: CompileClock
    split: Dict[str, float]
    warm: tuple                          # compile marks around warm-up


def setup(cell: Cell, seed: int, max_prompt: int) -> Server:
    """Weights from ``seed``, the latency model fitted on this chip, the
    engine and loop, every program the traffic can use compiled or
    loaded, and the served path's small programs run once."""
    from repro.core.policies import make, make_discipline
    from repro.engine.engine import Engine
    from repro.launch.serve import fit_latency_model
    from repro.serving import ServeLoop
    arch, spec, mix = cell.arch, cell.spec, cell.mix
    clock, split = CompileClock(), {}
    t = time.perf_counter()

    def lap(name):
        nonlocal t
        split[name] = time.perf_counter() - t
        t = time.perf_counter()

    params = arch.make_params(spec, seed)
    lap("init")
    cfg = arch.program_config(spec)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 3]))
    lat_model = fit_latency_model(cfg, params, spec.max_slots, rng)
    lap("fit")
    eng = Engine(cfg, params, max_slots=spec.max_slots,
                 max_seq_len=spec.max_seq_len, block_size=spec.block_size,
                 num_blocks=spec.num_blocks)
    pol = make(mix.policy, model=lat_model, max_batch=spec.max_slots)
    loop = ServeLoop(eng, pol, model=lat_model,
                     discipline=make_discipline(mix.discipline))
    lap("engine")
    c0 = clock.mark()
    prewarm(eng, max_prompt)
    loop.start()
    warm_serve(loop, spec.vocab, spec.max_slots, max_prompt, rng)
    lap("warm")
    return Server(params, eng, loop, clock, split, (c0, clock.mark()))


@dataclasses.dataclass
class Offered:
    """What one offer of a request list gave: the streams, the loop time
    the requests' due times count from, when serving stopped (seconds
    from that base), and the compile marks around the window."""
    streams: dict
    t_base: float
    t_end: float
    window_marks: tuple
    gauges: list                         # the loop's gauges in the window


def offer(srv: Server, reqs, ramp_s: float, seconds: float,
          drain_cap_s: float, rec: Optional[Record] = None,
          prof_dir: Optional[str] = None) -> Offered:
    """Submit ``reqs`` open-loop (each at its due time) and drive the
    loop through the ramp and the window, then until every request due
    in the window has finished or ``drain_cap_s`` has passed since the
    window closed (since the profiler stopped, in a traced run).  With a
    ``prof_dir`` the window runs under the profiler."""
    loop, clock = srv.loop, srv.clock
    t_base = loop.now() + 0.05
    streams = {r.idx: loop.submit(r.prompt, max_new_tokens=r.output_len,
                                  slo=_slo(r.slo), task_type=r.task_type,
                                  arrival_time=t_base + r.due)
               for r in reqs}
    w0, w1 = t_base + ramp_s, t_base + ramp_s + seconds
    cap = None
    due = [r.idx for r in reqs if ramp_s <= r.due < ramp_s + seconds]
    dues = sorted(r.due + t_base for r in reqs)
    phase, marks, ann, nxt = 0, [], None, 0
    while True:
        now = loop.now()
        if phase == 0 and now >= w0:
            if prof_dir is not None:
                # host spans are TraceMe level 1; no Python tracer
                opts = jax.profiler.ProfileOptions()
                opts.host_tracer_level, opts.python_tracer_level = 1, 0
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(prof_dir, profiler_options=opts)
                ann = jax.profiler.TraceAnnotation("bench.window")
                ann.__enter__()
                rec.on = True
            marks.append(clock.mark())
            phase = 1
        if phase == 1 and now >= w1:
            marks.append(clock.mark())
            phase = 2
            if prof_dir is not None:
                rec.on = False
                if loop._inflight is not None:
                    jax.block_until_ready(loop._inflight.tokens)
                ann.__exit__(None, None, None)
                jax.profiler.stop_trace()
            gauges = [g for g in loop.metrics.gauges if w0 <= g.t < w1]
            if rec is not None:
                rec.gauges = gauges
            # the drain starts once the profiler has written its trace,
            # which can take tens of seconds in which nothing is served
            cap = loop.now() + drain_cap_s
        if phase == 2 and (now >= cap or all(streams[i].done for i in due)):
            break
        loop.tick()
        if loop._idle():
            while nxt < len(dues) and dues[nxt] <= now:
                nxt += 1
            gap = (dues[nxt] - loop.now()) if nxt < len(dues) else 0.001
            time.sleep(min(max(gap, 0.0), 0.005))
    t_end = loop.now() - t_base
    loop.drain()
    return Offered(streams, t_base, t_end, tuple(marks), gauges)


def outcomes(off: Offered) -> Dict[int, traffic.Outcome]:
    return {i: traffic.Outcome(times=[ev.t - off.t_base for ev in s.events],
                               rejected=s.error is not None)
            for i, s in off.streams.items()}


def finished(reqs, off: Offered) -> Dict[int, tuple]:
    """(prompt, served tokens) of every request served in full."""
    out = {}
    for r in reqs:
        s = off.streams[r.idx]
        if s.done and s.error is None and len(s.tokens) == r.output_len:
            out[r.idx] = (r.prompt, s.tokens)
    return out


def compare(cell: Cell, params, done: Dict[int, tuple], seed: int,
            control: bool = False) -> dict:
    """The sample's widest served-token gap under the float32 reference
    (and, with ``control``, the fp8 control's)."""
    pick = reference.pick_sample(done, seed,
                                 cell.check.get("min_tokens", 512),
                                 cell.check.get("max_requests", 8))
    ref = cell.arch.Reference(cell.spec, params, control=control)
    widest, widest_ctl, n_tok = 0.0, 0.0, 0
    for i in pick:
        g, c = ref.gaps(*done[i])
        widest = max(widest, float(g.max()))
        if control:
            widest_ctl = max(widest_ctl, float(c.max()))
        n_tok += len(g)
    return {"requests": len(pick), "tokens": n_tok, "gap": widest,
            "control_gap": widest_ctl if control else None}


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        devices, t_proc0: float, log=print, control: bool = False) -> dict:
    """One run; returns the result line's object (without printing).
    With ``control`` the check also reads the fp8 control's widest gap
    on the same sample (``control_gap``); a benchmark run never does."""
    spec, mix = cell.spec, cell.mix
    dev = devices[0]
    peak = work.peaks(dev.device_kind)
    t_setup0 = time.perf_counter()
    reqs = traffic.generate(mix, seed, seconds, spec.vocab,
                            max_total=spec.max_seq_len)
    srv = setup(cell, seed, max(r.input_len for r in reqs))
    rec = Record(arch=cell.arch, spec=spec, peak=peak)
    if trace:
        _instrument(srv.loop, srv.eng, rec)
    prof_dir = tempfile.mkdtemp(prefix="onchip_trace_") if trace else None
    setup_s = time.perf_counter() - t_proc0
    off = offer(srv, reqs, mix.ramp_s, seconds, mix.drain_cap_s, rec,
                prof_dir)

    # ---------------------------------------------------------- numbers
    e2e = traffic.window_metrics(reqs, outcomes(off), mix.ramp_s, seconds,
                                 off.t_end)
    e2e["setup_s"] = setup_s
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    done = finished(reqs, off)
    due = [r.idx for r in reqs if mix.ramp_s <= r.due < mix.ramp_s + seconds]
    unfinished = sum(1 for i in due if i not in done)
    free = [g.free_blocks for g in off.gauges]
    (c0, c1), (cw0, cw1) = srv.warm, off.window_marks
    log(f"setup_s={setup_s:.3f} split: start={t_setup0 - t_proc0:.3f} "
        + " ".join(f"{k}={v:.3f}" for k, v in srv.split.items())
        + f" warm_compiles={c1[0] - c0[0]} warm_compile_s={c1[2] - c0[2]:.3f}"
        f" warm_cache_loads={c1[1] - c0[1]} ramp_s={mix.ramp_s:g}")
    log(f"compiles_in_window={cw1[0] - cw0[0]} "
        f"cache_loads_in_window={cw1[1] - cw0[1]}")
    log("window: " + json.dumps(e2e))
    log(f"requests: generated={len(reqs)} due_in_window={len(due)} "
        f"finished={len(done)} unfinished_due={unfinished} "
        f"memory_peak_bytes={mem_peak} "
        f"bytes_limit={stats.get('bytes_limit')} "
        f"pool_blocks={srv.eng.num_blocks} "
        f"window_min_free_blocks={min(free) if free else None}")

    metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
               for m in cell.end_to_end if e2e.get(m["name"]) is not None}
    out = {"attempted": e2e["attempted"], "failed": e2e["failed"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if trace:
        metrics = _per_layer(cell, rec, prof_dir, device, out, log)

    # --------------------------------------------------------- the check
    params = srv.params
    del srv, off
    gc.collect()
    t = time.perf_counter()
    cmp = compare(cell, params, done, seed, control=control)
    limit = cell.check.get("logit_gap_limit")
    checks = {"unfinished": {"value": unfinished, "limit": 0},
              "logit_gap": {"value": cmp["gap"], "limit": limit}}
    correct = (unfinished == 0 and cmp["requests"] > 0
               and limit is not None and cmp["gap"] <= limit)
    log(f"check: {cmp['requests']} requests, {cmp['tokens']} served tokens "
        f"compared against the float32 reference in "
        f"{time.perf_counter() - t:.1f} s")
    out.update({"correct": correct, "metrics": metrics, "device": device,
                "compared": {"requests": cmp["requests"],
                             "tokens": cmp["tokens"]},
                "checks": checks})
    if control:
        out["control_gap"] = cmp["control_gap"]
    return out


def _step_starts(spans, name: str, lo: int, hi: int, n: int) -> List[int]:
    """The start of each ``name`` span in ``[lo, hi)``, in order: one per
    step record the harness kept in the window (``n``), or an error."""
    starts = sorted(s.start for s in spans
                    if s.name == name and lo <= s.start < hi)
    if len(starts) != n:
        raise RuntimeError(
            f"the trace holds {len(starts)} {name} spans in the window "
            f"against {n} steps recorded: the host trace is not whole")
    return starts


def _runs(mods, program: str, lo: int, hi: int) -> int:
    """Runs of a step program that start in ``[lo, hi)``."""
    return sum(1 for e in mods if program in e.name and lo <= e.start < hi)


def _traced_part(rec: Record, lo: int, hi: int) -> tuple:
    """The part of the window ``[lo, hi)`` that the trace holds, and the
    decode and prefill program runs in it.  Where the device plane holds
    fewer runs of a step program than the harness dispatched, or reports
    dropped trace buffers, it lost its events past some point: the window
    ends where ``trace_reduce.traced`` says, and ``rec`` keeps the steps
    whose host span starts before that, no more of them than the part
    holds runs (the last steps dispatched may have lost theirs).  A whole
    trace keeps ``[lo, hi)``."""
    from . import trace_reduce
    ops = rec.trace.ops.get(rec.device, [])
    mods = rec.trace.modules.get(rec.device, [])
    decode_t = _step_starts(rec.trace.spans, "bench.dispatch", lo, hi,
                            len(rec.decode_ctx))
    prefill_t = _step_starts(rec.trace.spans, "bench.prefill", lo, hi,
                             len(rec.prefill_n))
    if rec.device is not None and (
            _runs(mods, DECODE_PROGRAM, lo, hi) < len(decode_t)
            or _runs(mods, PREFILL_PROGRAM, lo, hi) < len(prefill_t)
            or rec.trace.dropped.get(rec.device)):
        lo, hi = trace_reduce.traced(ops + mods, lo, hi)
        if hi <= lo:
            raise RuntimeError("the trace holds no device event in the window")
        rec.decode_ctx = [r for r, t in zip(rec.decode_ctx, decode_t)
                          if t < hi][:_runs(mods, DECODE_PROGRAM, lo, hi)]
        rec.prefill_n = [r for r, t in zip(rec.prefill_n, prefill_t)
                         if t < hi][:_runs(mods, PREFILL_PROGRAM, lo, hi)]
    rec.window_ns = (lo, hi)
    return (_runs(mods, DECODE_PROGRAM, lo, hi),
            _runs(mods, PREFILL_PROGRAM, lo, hi))


def _per_layer(cell: Cell, rec: Record, prof_dir: str, device: dict,
               out: dict, log) -> dict:
    """Reduce the window's trace over the part of it that the trace holds
    (``_traced_part``): device busy time, the breakdown, and every
    per-layer reader's number.  ``device`` gains ``busy_s`` and
    ``window_s`` over that part and ``traced_share``, that part over the
    whole window; host counters (gauges, decide times) keep the whole
    window."""
    from . import trace_reduce
    files = glob.glob(os.path.join(prof_dir, "**", "*.xplane.pb"),
                      recursive=True)
    rec.trace = trace_reduce.load(files[0])
    shutil.rmtree(prof_dir, ignore_errors=True)
    win = next(s for s in rec.trace.spans if s.name == "bench.window")
    rec.device = (rec.trace.devices or [None])[0]
    dispatched = (len(rec.decode_ctx), len(rec.prefill_n))
    decode_runs, prefill_runs = _traced_part(rec, win.start, win.end)
    lo, hi = rec.window_ns
    ops = rec.trace.ops.get(rec.device, [])
    busy = trace_reduce.busy(ops, lo, hi)
    device["busy_s"] = trace_reduce.total(busy) / 1e9
    device["window_s"] = (hi - lo) / 1e9
    device["traced_share"] = (hi - lo) / win.dur
    top = sorted(trace_reduce.time_by_name(ops, lo, hi).items(),
                 key=lambda kv: -kv[1])[:10]
    gaps = trace_reduce.labelled_gaps(trace_reduce.gaps(busy, lo, hi),
                                      rec.trace.spans)
    out["breakdown"] = {"device_ops": [[n, ns / 1e9] for n, ns in top],
                        "idle_gaps": [[n, ns / 1e9] for n, ns in gaps]}
    log("per-layer counts: " + json.dumps({
        "decode_steps": len(rec.decode_ctx), "decode_modules": decode_runs,
        "prefills": len(rec.prefill_n), "prefill_modules": prefill_runs,
        "decode_dispatched": dispatched[0],
        "prefills_dispatched": dispatched[1],
        "decides": len(rec.decide_s), "ticks": len(rec.gauges)}))
    readers = _load_readers(cell)
    metrics = {}
    for m in cell.per_layer:
        v = readers[m["name"]].read(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    return metrics
