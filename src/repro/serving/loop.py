"""Async streaming serving loop: overlapped host scheduling + device
execution over the slot-pool engine.

Everything before this module was batch-oriented — ``Engine.run_policy``
consumes a fixed workload list and syncs the device every step, so
host-side scheduling (annealing, admission, block accounting) serializes
with execution and latency is charged on the engine's step-time clock.
:class:`ServeLoop` turns the same engine into a long-running service:

* **Submission + arrival-timed ingestion** — ``submit()`` enqueues a
  request (thread-safe) with an optional future ``arrival_time``; the
  loop releases it into the waiting queue once that instant passes on
  the wall clock, so Poisson traces replay in real time.
* **Token streaming** — every generated token is delivered through the
  request's :class:`~repro.serving.stream.TokenStream` with a wall-clock
  timestamp: TTFT / TBT / e2e are *measured at the delivery boundary*,
  exactly what a streaming client observes.
* **Overlapped execution** (``overlap=True``) — decode round ``N+1`` is
  dispatched from device-resident token state *before* round ``N``'s
  sampled ids are read back (the engine's fused decode+sample keeps them
  on device).  While the device computes, the host delivers round
  ``N-1``'s tokens, runs the scheduling policy, updates block accounting
  and the prefix index.  One decode round of lookahead means host state
  lags the device by at most one round; a request that finishes mid-
  lookahead has its overshoot token dropped at readback (identity-
  guarded delivery), and requests whose output budget is provably
  exhausted are excluded from the next dispatch up front, so greedy
  decoding is token-for-token identical to the synchronous mode.
* **Pow-2 batch buckets** (``bucket_batches=True``, paged engines) —
  each round is dispatched over the smallest power-of-two slot prefix
  covering every active slot, so arrival jitter changes the compiled
  shape only at bucket boundaries (at most ``log2(max_slots)``
  compilations, pre-warmed in ``start()``).
* **Chunk-as-tick prefill** — every tick executes the active
  :class:`~repro.core.policies.ExecutionDiscipline`'s ``StepPlan``:
  staged admissions (``Engine.begin_prefill``) advance chunk-by-chunk
  under ``ChunkedPrefill(n)`` / ``dynamic-chunk`` while the running
  decode round dispatches in the same tick, so a long prompt no longer
  stalls streaming TBT for its whole prefill.  ``StallingPrefill``
  (default) completes each prefill within its admission tick.

The scheduling brain is unchanged: the same v2
:class:`~repro.core.policies.SchedulingPolicy` objects drive admission
and preemption through :meth:`Engine.build_view`, with SLO budgets
shifted by true wall-clock waiting.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.latency_model import LinearLatencyModel
from repro.core.policies import (ChunkedPrefill, make_discipline,
                                 normalize_decision, resolve_policy)
from repro.core.slo import SLO, Request
from repro.engine.engine import Engine, _bucket
from repro.engine.request import Phase, RuntimeRequest
from repro.serving.metrics import (RequestTimeline, ServingMetrics,
                                   StepGauge)
from repro.serving.stream import TokenStream


class UnsupportedDisciplineError(NotImplementedError):
    """The requested discipline cannot run on this engine.  Since the
    step-planner refactor the streaming loop executes chunked and
    adaptive disciplines natively (prefill chunks ride the tick plan
    alongside decode dispatches), so this is raised only for the one
    genuinely unsupported combination: chunked prefill on an MLA arch,
    which has no chunked forward path.  Subclassing
    ``NotImplementedError`` keeps pre-existing callers' handlers
    working."""


class _Ticket:
    """One in-flight decode round: the device array of sampled ids plus
    the (slot, request, expected-index) participants recorded at
    dispatch time.  Identity-guarded consumption: a participant whose
    request finished, was preempted, or whose slot was reassigned while
    the round was in flight has its token dropped."""

    __slots__ = ("tokens", "parts", "width", "t_dispatch")

    def __init__(self, tokens, parts, width, t_dispatch):
        self.tokens = tokens
        self.parts: List[Tuple[int, RuntimeRequest, int]] = parts
        self.width = width
        self.t_dispatch = t_dispatch


class ServeLoop:
    """Long-running streaming serving loop over an :class:`Engine`.

    Parameters
    ----------
    engine:
        A fresh engine (its slot pool and KV pool become the service's).
    policy:
        v2 policy object or any ``repro.core.policies.make`` registry
        key (``"fcfs"``, ``"slo-reanneal[:jax]"``, ``"slo-preempt"``…).
    model:
        Latency model for slack/budget projections (policies that carry
        their own are used as fallback).
    discipline:
        :class:`~repro.core.policies.ExecutionDiscipline` or registry
        key (``"stall"``, ``"chunked:32"``).  Default resolution
        matches ``Engine.run_policy``: the policy's own discipline
        (``dynamic-chunk``), else the engine's ``chunked_prefill``
        setting, else stalling whole-prompt prefill.  Chunked on an MLA
        arch raises :class:`UnsupportedDisciplineError`.
    overlap:
        Dispatch round ``N+1`` before syncing round ``N`` (one-step
        lookahead).  ``False`` = synchronous reference mode: identical
        code path, but every round is read back immediately.
    bucket_batches:
        Pad decode dispatches to pow-2 slot-prefix buckets (paged
        engines only) instead of always running the full slot pool.
    id_base:
        Starting request id — fleet members get disjoint ranges so one
        aggregated metrics/result namespace never collides.
    """

    def __init__(self, engine: Engine, policy="fcfs", *,
                 model: Optional[LinearLatencyModel] = None,
                 discipline=None, overlap: bool = True,
                 bucket_batches: bool = True,
                 metrics: Optional[ServingMetrics] = None,
                 id_base: int = 0):
        self.eng = engine
        self.pol, self.preemptive = resolve_policy(
            policy, model=model, max_batch=engine.max_slots)
        self.model = model if model is not None \
            else getattr(self.pol, "model", None)
        if discipline is None:
            # same resolution as Engine.run_policy: a policy carrying
            # its own discipline (dynamic-chunk) wins, then the
            # engine's chunked_prefill default — object identity is
            # preserved so adaptive retuning reaches the tick planner
            discipline = getattr(self.pol, "discipline", None)
        if discipline is None and engine.chunked_prefill:
            discipline = ChunkedPrefill(engine.chunked_prefill)
        self.disc = make_discipline(discipline)
        if self.disc.chunk_size and engine.cfg.mla is not None:
            raise UnsupportedDisciplineError(
                f"{self.disc!r} is unsupported for MLA archs (no "
                "chunked forward path); use whole-prompt (stalling) "
                "prefill")
        self.overlap = overlap
        self.bucket_batches = bucket_batches and engine.paged
        self.metrics = metrics if metrics is not None else ServingMetrics()

        self._lock = threading.Lock()
        self._inbox: deque = deque()         # submitted, not yet ingested
        self._future: List[RuntimeRequest] = []   # ingested, arrival ahead
        self._waiting: List[RuntimeRequest] = []
        self._streams: Dict[int, TokenStream] = {}
        self._requests: Dict[int, RuntimeRequest] = {}
        self._inflight: Optional[_Ticket] = None
        self._feed = None                    # [max_slots, 1] device ids
        self._t0: Optional[float] = None
        # id_base offsets request ids so loops sharing a fleet-wide
        # metrics/result namespace never collide
        self._next_id = id_base
        self._stall_spins = 0
        self._stopped = False
        self._admit_waits: List[float] = []  # first admissions this tick

    # ------------------------------------------------------------- clock
    def now(self) -> float:
        """Wall-clock seconds since ``start()``."""
        if self._t0 is None:
            raise RuntimeError("loop not started")
        return time.perf_counter() - self._t0

    # ------------------------------------------------------------ warmup
    def start(self, warm_lengths: Sequence[int] = ()):
        """Compile-warm the dispatch buckets (and optionally the prefill
        length buckets a trace will hit) and stamp the epoch.  Warmup
        runs *before* the clock starts so first-seen compiles never
        pollute measured TTFT/TBT."""
        if self._t0 is not None:
            return self
        eng = self.eng
        if self._feed is None:
            # from the first round on the feed carries the dispatch
            # output's sharding; warming with any other would make a
            # mesh engine compile its decode step again mid-serve
            self._feed = eng.replicated(
                jnp.zeros((eng.max_slots, 1), jnp.int32))
        widths = {eng.max_slots}
        if self.bucket_batches:
            w = 1
            while w <= eng.max_slots:
                widths.add(min(w, eng.max_slots))
                w *= 2
        idle = np.zeros(eng.max_slots, bool)
        for w in sorted(widths):
            eng.dispatch_decode(self._feed, idle, width=w)
        exact = bool(eng.cfg.ssm_layers)     # SSM archs prefill unpadded
        for n in sorted({int(n) if exact else _bucket(int(n))
                         for n in warm_lengths}):
            if ("prefill", n) in eng._warm or n >= eng.max_seq_len:
                continue
            toks = jnp.zeros((1, n), jnp.int32)
            with eng._warm_span("prefill", n):
                if eng.paged:
                    eng._warm_paged(eng._prefill_fn, toks, n, 0)
                else:
                    eng._prefill_fn(eng.params, toks,
                                    n)[0].block_until_ready()
            eng._warm.add(("prefill", n))
        # chunked disciplines: pre-warm the chunk buckets a plan can hit
        # (every pow-2 bucket up to the largest chunk, so ragged final
        # chunks are covered too).  Adaptive policies may retune up to
        # their max_chunk.
        C = self.disc.chunk_size
        if C and eng.paged:
            hi = _bucket(max(C, getattr(self.pol, "max_chunk", C)))
            L = 16
            while L <= hi and L < eng.max_seq_len:
                if ("chunk", L) not in eng._warm:
                    toks = jnp.zeros((1, L), jnp.int32)
                    with eng._warm_span("chunk", L):
                        eng._warm_paged(eng._chunk_fn, toks, 0, L)
                    eng._warm.add(("chunk", L))
                L *= 2
        self._t0 = time.perf_counter()
        return self

    # -------------------------------------------------------- submission
    def submit(self, prompt_tokens, *, max_new_tokens: int,
               slo: Optional[SLO] = None, task_type: str = "chat",
               arrival_time: Optional[float] = None,
               request: Optional[Request] = None,
               on_token=None) -> TokenStream:
        """Enqueue one request (thread-safe) and return its token stream.

        ``arrival_time`` (loop-relative seconds) schedules a future
        arrival — trace replay submits the whole workload up front and
        the loop releases each request when its instant passes on the
        wall clock.  ``None`` = arrive immediately.  ``request`` passes
        a pre-built :class:`Request` (its ``arrival_time`` is used when
        the kwarg is None)."""
        prompt = np.asarray(prompt_tokens, np.int32)
        with self._lock:
            rid = self._next_id
            self._next_id += 1
        if request is None:
            request = Request(
                req_id=rid, task_type=task_type, input_len=len(prompt),
                slo=slo if slo is not None else SLO(),
                output_len=max_new_tokens,
                arrival_time=arrival_time if arrival_time is not None
                else 0.0)
        else:
            request.req_id = rid
            if arrival_time is not None:
                request.arrival_time = arrival_time
        rt = RuntimeRequest(request=request, prompt_tokens=prompt,
                            max_new_tokens=int(max_new_tokens))
        stream = TokenStream(rid, on_token=on_token)
        with self._lock:
            self._streams[rid] = stream
            self._requests[rid] = rt
            self._inbox.append(rt)
        return stream

    def submit_trace(self, pairs) -> List[TokenStream]:
        """Submit a ``[(Request, prompt_tokens)]`` trace (the
        ``data.synthetic`` token-workload format) for wall-clock
        replay."""
        return [self.submit(toks, max_new_tokens=r.planning_output_len(),
                            request=r) for r, toks in pairs]

    # --------------------------------------------------------- ingestion
    def _reject(self, rt: RuntimeRequest, reason: str, t: float):
        stream = self._streams[rt.req_id]
        stream.submit_time = t
        stream.fail(reason, t)
        self.metrics.on_finish(rt.request, RequestTimeline(
            req_id=rt.req_id, task_type=rt.request.task_type,
            arrival=rt.request.arrival_time, submit=t, first_token=None,
            finish=None, n_tokens=0, tbt=[], rejected=True))

    def _ingest(self, t: float):
        """Move submissions into the arrival schedule, and release every
        request whose arrival instant has passed into the waiting
        queue — stamping ``submit_time`` on the loop clock so policy
        budget shifting sees true wall waiting."""
        with self._lock:
            newly = list(self._inbox)
            self._inbox.clear()
        for rt in newly:
            eng = self.eng
            if rt.input_len >= eng.max_seq_len:
                self._reject(rt, f"prompt length {rt.input_len} >= "
                                 f"max_seq_len {eng.max_seq_len}", t)
            elif eng.paged and eng._blocks_needed(rt) > eng.pool.total:
                self._reject(rt, f"needs {eng._blocks_needed(rt)} KV "
                                 f"blocks, pool holds {eng.pool.total}", t)
            else:
                self._future.append(rt)
        if newly:
            self._future.sort(key=lambda rt: rt.request.arrival_time)
        while self._future and self._future[0].request.arrival_time <= t:
            rt = self._future.pop(0)
            # queueing from the true arrival instant counts toward
            # budgets — a request that arrived mid-step waited too
            rt.submit_time = min(rt.request.arrival_time, t)
            rt.request.submit_time = rt.submit_time
            self._streams[rt.req_id].submit_time = rt.submit_time
            self._waiting.append(rt)

    # -------------------------------------------------------- scheduling
    def _retune(self):
        """Let an adaptive policy resize its chunk against the current
        active set on ticks where ``decide()`` doesn't run (empty
        queue) — same hook as the batch loop and the event core."""
        fn = getattr(self.pol, "retune", None)
        if fn is not None and not all(self.eng.slot_free):
            fn(self.eng.build_view([], self.disc, self.model))

    def _schedule(self):
        """One policy decision over the live view: preempt, then reserve
        blocks and *stage* admissions (``begin_prefill``).  The staged
        prefills advance through the tick plan in :meth:`tick` — whole-
        prompt in one tick under stall, chunk-by-chunk alongside decode
        dispatches under a chunked discipline."""
        eng = self.eng
        if not self._waiting:
            self._retune()
            return False
        free = eng.free_slots()
        if not free and not (self.preemptive and not all(eng.slot_free)):
            self._retune()
            return False
        view = eng.build_view(self._waiting, self.disc, self.model)
        with TraceAnnotation("sched.decide", waiting=len(self._waiting)):
            decision = self.pol.decide(view)
        admit, preempt = normalize_decision(decision, view)
        active_rts = eng.active_requests()
        did = False
        for j in preempt:
            vict = active_rts[j]
            # re-prefill must fit: prompt + generated + next token
            if vict.input_len + len(vict.generated) + 1 >= eng.max_seq_len:
                continue
            eng.preempt(vict)
            self._waiting.append(vict)       # view indices stay valid
            did = True
        free = eng.free_slots()
        sel = []
        for j in admit:
            if len(sel) >= len(free):
                break
            # reserve atomically (alias cached prefix + alloc the rest)
            # so same-tick admissions never race a probe against a later
            # allocation
            if eng.paged and not eng._reserve_blocks(self._waiting[j]):
                continue                     # out of KV blocks: wait
            sel.append(j)
        chosen = [self._waiting[j] for j in sel]
        for j in sorted(sel, reverse=True):
            self._waiting.pop(j)
        for rt, slot in zip(chosen, free):
            first = rt.admit_time is None
            # stage only: the prefill runs via this tick's plan below
            eng.begin_prefill(rt, slot)
            if first:
                self._admit_waits.append(rt.admit_time - rt.submit_time)
            did = True
        return did

    def _after_prefill(self, rt: RuntimeRequest):
        """Deliver the token(s) a synchronous prefill produced (one, or
        the catch-up after a preemption re-prefill) and seed the device
        feed for the next decode round."""
        t = self.now()
        stream = self._streams[rt.req_id]
        for idx in range(len(stream.events), len(rt.generated)):
            stream.push(rt.generated[idx], t)
        if rt.phase is Phase.FINISHED:       # finished at prefill
            self._finish(rt, t, slot_done=True)
        else:
            self._feed = self._feed.at[rt.slot, 0].set(rt.generated[-1])

    # ---------------------------------------------------------- dispatch
    def _inflight_count(self, rt: RuntimeRequest) -> int:
        """Decode rounds in flight for ``rt`` (0 or 1): its host token
        count lags the device by this many tokens."""
        if self._inflight is None:
            return 0
        return sum(1 for s, r, i in self._inflight.parts
                   if r is rt and i == len(rt.generated))

    def _dispatch_round(self) -> Optional[_Ticket]:
        """Dispatch one fused decode+sample round over the active slots
        (minus requests whose output budget is provably exhausted after
        the in-flight round) without waiting for it."""
        eng = self.eng
        parts: List[Tuple[int, RuntimeRequest, int]] = []
        active = np.zeros(eng.max_slots, bool)
        for slot, rt in enumerate(eng.slot_req):
            if rt is None or rt.phase is not Phase.RUNNING:
                continue
            ahead = self._inflight_count(rt)
            if len(rt.generated) + ahead >= rt.max_new_tokens:
                continue                     # will finish at readback
            active[slot] = True
            parts.append((slot, rt, len(rt.generated) + ahead))
        if not parts:
            return None
        width = eng.max_slots
        if self.bucket_batches:
            width = min(_bucket(max(s for s, _, _ in parts) + 1, lo=1),
                        eng.max_slots)
        toks = eng.dispatch_decode(
            self._feed, active, width=width,
            lookahead=1 if self._inflight is not None else 0)
        self._feed = self._feed.at[:width, 0].set(toks)
        return _Ticket(toks, parts, width, self.now())

    # ----------------------------------------------------------- consume
    def _consume(self, ticket: _Ticket):
        """Read back one round's sampled ids (syncing the device up to
        that round) and deliver them with wall timestamps."""
        with TraceAnnotation("serve.consume", width=ticket.width):
            with self.eng.sync_span("serve.sync"):
                toks = np.asarray(ticket.tokens)
            t = self.now()
            for slot, rt, idx in ticket.parts:
                # identity guard: deliver only if the request is still the
                # running occupant of this slot and no token landed since
                # dispatch (preempted/finished/reassigned -> drop overshoot)
                if (rt.phase is not Phase.RUNNING or rt.slot != slot
                        or len(rt.generated) != idx):
                    continue
                self._deliver(rt, int(toks[slot]), t)

    def _deliver(self, rt: RuntimeRequest, tok: int, t: float):
        eng = self.eng
        rt.generated.append(tok)
        self._streams[rt.req_id].push(tok, t)
        if (eng.eos >= 0 and tok == eng.eos) or \
                len(rt.generated) >= rt.max_new_tokens:
            rt.phase = Phase.FINISHED
            rt.finish_time = t
            eng.finish_slot(rt)
            self._finish(rt, t, slot_done=False)

    def _finish(self, rt: RuntimeRequest, t: float, slot_done: bool):
        stream = self._streams[rt.req_id]
        stream.close(t)
        evs = stream.events
        self.metrics.on_finish(rt.request, RequestTimeline(
            req_id=rt.req_id, task_type=rt.request.task_type,
            arrival=rt.request.arrival_time, submit=rt.submit_time,
            first_token=evs[0].t if evs else None,
            finish=evs[-1].t if evs else None,
            n_tokens=len(evs), tbt=stream.tbts(),
            preemptions=rt.preemptions, cached_tokens=rt.cached_tokens,
            admit=rt.admit_time))

    # -------------------------------------------------------------- tick
    def _idle(self) -> bool:
        return (self._inflight is None and all(self.eng.slot_free)
                and not self._waiting)

    def _done(self) -> bool:
        with self._lock:
            inbox = len(self._inbox)
        return inbox == 0 and not self._future and self._idle()

    def _run_prefill_plan(self) -> int:
        """Advance every staged prefill by its planned span (the
        streaming half of ``Engine.execute_step`` — decode runs through
        the overlapped dispatch path instead).  The prefill jits chain
        after any in-flight decode round, so device order stays valid;
        a completing span delivers the first token and seeds the
        dispatch feed.  Returns prompt tokens computed this tick."""
        eng = self.eng
        plan = eng.plan_step(self.disc)
        done = 0
        for it in plan.prefills:
            rt = eng.slot_req[it.ref]
            if rt is None or rt.phase is not Phase.PREFILLING:
                continue
            eng.prefill_step(rt, it.length)
            done += it.length
            if rt.phase is not Phase.PREFILLING:     # completed
                self._after_prefill(rt)
        return done

    def tick(self):
        """One serving iteration: ingest -> schedule (stage admissions)
        -> prefill plan spans -> dispatch round N -> deliver round N-1
        (overlap) or round N (sync) -> gauges.  Under a chunked
        discipline a long prompt's chunk and the running decode round
        share every tick (chunk-as-tick)."""
        with TraceAnnotation("serve.tick"):
            self._tick()

    def _tick(self):
        eng = self.eng
        t0, warms, sync = time.perf_counter(), eng.warm_runs, eng.sync_s
        t = self.now()
        with TraceAnnotation("serve.ingest"):
            self._ingest(t)
        self.eng.clock = t          # engine stamps land on the wall clock
        with TraceAnnotation("serve.schedule"):
            admitted = self._schedule()
        pre_tok = self._run_prefill_plan()
        ticket = self._dispatch_round()
        prev, self._inflight = self._inflight, ticket
        if prev is not None:
            self._consume(prev)
        if not self.overlap and ticket is not None:
            self._consume(ticket)
            self._inflight = None
        waits, self._admit_waits = tuple(self._admit_waits), []
        self.metrics.on_gauge(StepGauge(
            t=t, queue_depth=len(self._waiting),
            active=sum(not f for f in self.eng.slot_free),
            free_blocks=self.eng.pool.available if self.eng.paged else -1,
            dispatch_width=ticket.width if ticket else 0,
            overlapped=prev is not None and ticket is not None,
            prefill_tokens=pre_tok,
            prefilling=sum(1 for rt in self.eng.slot_req
                           if rt is not None
                           and rt.phase is Phase.PREFILLING),
            warms=eng.warm_runs - warms,
            tick_s=time.perf_counter() - t0, sync_s=eng.sync_s - sync,
            queue_waits=waits))
        # stall detection: completely idle with a non-empty queue and a
        # policy that admits nothing (matches the batch loop's guard)
        if (ticket is None and self._inflight is None and self._waiting
                and not admitted and all(self.eng.slot_free)):
            self._stall_spins += 1
            if self._stall_spins > 4:
                rt = self._waiting[0]
                if self.eng.paged and all(
                        self.eng._unique_blocks_needed(w)
                        > self.eng._admission_blocks()
                        for w in self._waiting):
                    raise ValueError(
                        f"request {rt.req_id} needs "
                        f"{self.eng._unique_blocks_needed(rt)} KV blocks "
                        f"but only {self.eng._admission_blocks()} exist")
                raise RuntimeError(
                    "admission stalled: policy admitted nothing while "
                    "the loop was idle")
        else:
            self._stall_spins = 0

    def serve(self, poll: float = 0.0002):
        """Run until every submitted request has completed (and no
        future arrivals remain).  Between idle ticks the loop sleeps to
        the next scheduled arrival."""
        self.start()
        while not self._done():
            self.tick()
            if self._idle():
                with self._lock:
                    empty_inbox = not self._inbox
                if self._future and empty_inbox:
                    gap = self._future[0].request.arrival_time - self.now()
                    if gap > 0:
                        time.sleep(min(gap, 0.05))
                elif empty_inbox and not self._future:
                    continue            # _done() will see it
                else:
                    time.sleep(poll)
        return self.results()

    def drain(self):
        """Consume any in-flight round (used when driving ``tick()``
        manually)."""
        if self._inflight is not None:
            self._consume(self._inflight)
            self._inflight = None

    # ------------------------------------------------------------ output
    def results(self) -> Dict[int, dict]:
        """Engine-style result dict over every completed request."""
        done = [rt for rt in self._requests.values()
                if rt.phase is Phase.FINISHED]
        out = self.eng._collect(done)
        for rid in out:
            out[rid]["met_wall"] = self.metrics.met(rid)
        return out

    def streams(self) -> Dict[int, TokenStream]:
        return dict(self._streams)
