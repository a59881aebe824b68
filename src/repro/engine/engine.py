"""Slot-based continuous-batching serving engine over the JAX model.

Static-shape design (TPU-friendly): a fixed pool of ``max_slots`` request
slots; prefills are padded to power-of-two length buckets; the decode
step always runs over the full slot pool with inactive slots masked.

KV memory comes in two layouts. The default is a **block-paged pool**
(vLLM-style): one shared page array per layer (K/V kv-head-major,
``[kv, num_blocks, block_size, hd]``) plus per-slot block tables, sized
in *tokens* rather than ``max_slots × max_seq_len``. Prefill K/V is written
*in place* into the slot's pages (an O(prompt) scatter under jit buffer
donation — no per-prefill full-length cache allocation and no O(pool)
commit copy), blocks are allocated on admit and freed on finish/preempt,
and decode attends through the block table (the Pallas paged
flash-decode kernel on TPU). Admission is memory-aware: a request is
admitted only while free blocks cover its prompt + output budget, and
the block-pool occupancy is exposed to policies through
``SchedulerView.free_blocks``. ``paged=False`` restores the dense
``max_slots × max_seq_len`` layout (kept for comparison benchmarks);
SSM-only archs always use it — their state is O(1) in sequence length,
so there is nothing to page.

Scheduling is delegated to the v2 API (:mod:`repro.core.policies`):
``run_policy`` accepts any :class:`SchedulingPolicy` — the same objects
that drive the discrete-event core — builds a :class:`SchedulerView` of
the pending and running sets each step, and honors admit *and* preempt
decisions (evicted requests lose their KV and are re-prefilled over
prompt + generated tokens).  ``run_fcfs`` / ``run_planned`` /
``run_priority`` are thin wrappers over it.

Execution is plan-driven (chunk-as-tick): each round the active
:class:`ExecutionDiscipline` emits a :class:`StepPlan` — one prefill
span per slot mid-prefill (``Phase.PREFILLING``, staged by
``begin_prefill``) plus one decode item per running slot — and
``execute_step`` advances the prefill spans then runs a single decode
round, so under ``ChunkedPrefill(n)`` a long prompt's chunks ride the
same ticks as the running decodes (Sarathi-style mixed batches) while
``StallingPrefill`` completes each prefill in one tick.  ``run_policy``,
the discrete-event core and the streaming ``ServeLoop`` are all thin
drivers of this one plan/execute cycle.

Every prefill/decode step is timed and fed to the ``LatencyProfiler`` so
the paper's linear latency model can be fit from *this* engine's behaviour
(hardware adaptation: coefficients are re-fit per device type).
"""
from __future__ import annotations

import contextlib
import time
import warnings
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.latency_model import LinearLatencyModel
from repro.core.policies import (ChunkedPrefill, ExecutionDiscipline,
                                 FCFSPolicy, PlannedPolicy, SchedulerView,
                                 SchedulingPolicy, StallingPrefill,
                                 StepPlan, make_active_view,
                                 make_discipline, normalize_decision,
                                 resolve_policy)
from repro.core.profiler import LatencyProfiler
from repro.core.slo import meets_slo
from repro.engine.blocks import BlockPool
from repro.engine.prefix import RadixPrefixIndex
from repro.engine.request import Phase, RuntimeRequest
from repro.engine.sampling import sample
from repro.models.cache import (copy_page, init_cache, init_paged_cache,
                                paged_slot_len)
from repro.models.config import ModelConfig
from repro.models.model import (forward_chunk, forward_chunk_paged,
                                forward_decode, forward_decode_paged,
                                forward_full, forward_prefill_paged)


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class Engine:
    def __init__(self, cfg: ModelConfig, params, max_slots: int = 8,
                 max_seq_len: int = 512, eos_token: int = -1,
                 temperature: float = 0.0, seed: int = 0,
                 profiler: Optional[LatencyProfiler] = None,
                 chunked_prefill: int = 0, paged: Optional[bool] = None,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefix_cache: bool = True, mesh=None, parallelism=None,
                 kernel_mode: Optional[str] = None):
        """chunked_prefill > 0: split prompts into chunks of that size and
        interleave each chunk with a decode round for the running slots
        (Sarathi-style — new prompts no longer stall running decodes for
        their whole prefill).  Unsupported for MLA archs (falls back).

        ``paged`` (default: True whenever the arch has attention layers)
        selects the block-paged KV pool: ``num_blocks`` pages of
        ``block_size`` tokens each (+ the reserved null page), defaulting
        to the dense layout's capacity of ``max_slots`` full-length
        slots.  Shrinking ``num_blocks`` trades HBM for admission
        capacity — admission refuses requests whose prompt + output
        budget exceeds the free blocks.

        ``prefix_cache`` (default on for paged pure-attention archs)
        enables shared-prefix KV reuse: finished/prefilled prompts are
        indexed block-by-block in a radix trie, arriving prompts alias
        the longest cached block-aligned prefix (refcounted pages) and
        prefill only the unique suffix.  Divergent writes into a shared
        page copy-on-write.  Disabled automatically for SSM/hybrid
        (recurrent state is not block-addressable), MLA and
        sliding-window archs.

        ``mesh`` (a ``jax.sharding.Mesh``, e.g. from
        ``repro.launch.mesh.make_host_mesh``) turns on tensor-parallel
        SPMD execution: params shard per ``distributed.sharding.
        param_specs`` and the paged page arrays shard on the kv-head
        axis (``cache_specs`` paged layout) while ``pos`` /
        ``block_tables`` stay replicated, so every host-side path —
        BlockPool accounting, prefix reuse, copy-on-write — is
        untouched.  The jitted step fns pin their outputs
        (``out_shardings``): logits/tokens replicated, cache on its
        sharding, which also keeps buffer donation exact.  Requires the
        paged layout.  ``parallelism`` overrides the
        :class:`~repro.distributed.sharding.ParallelismConfig` (default:
        tp on the ``model`` axis, no FSDP — serving replicates what it
        cannot head-shard).  A one-device mesh places the engine on that
        device (how a fleet puts one replica on each chip).

        ``kernel_mode`` is this engine's paged-decode attention path
        (``kernels.ops``: ``pallas`` / ``interpret`` / ``ref``), resolved
        once by :func:`repro.kernels.ops.resolve_mode` — the compiled
        kernel on a TPU backend by default, the jnp gather reference
        elsewhere and at tp > 1 — and closed over by this engine's jits
        only; read it back from ``engine.kernel_mode``."""
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.eos = eos_token
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self.profiler = profiler
        self.clock = 0.0             # engine-internal wall clock
        if paged is None:
            paged = bool(cfg.attn_layers)
        self.paged = paged and bool(cfg.attn_layers)
        self.slot_free = [True] * max_slots
        self.slot_req: List[Optional[RuntimeRequest]] = [None] * max_slots
        if self.paged:
            self.block_size = block_size
            self.slot_len = paged_slot_len(cfg, max_seq_len, block_size)
            self.pages_per_slot = self.slot_len // block_size
            if num_blocks is None:
                num_blocks = max_slots * self.pages_per_slot + 1
            self.num_blocks = num_blocks
            self.pool = BlockPool(num_blocks)
            self._slot_blocks: List[List[int]] = [[] for _ in
                                                  range(max_slots)]
            self.cache = init_paged_cache(cfg, max_slots, max_seq_len,
                                          num_blocks, block_size)
            jit_kw = self._init_mesh(mesh, parallelism, kernel_mode)
            # the paged step fns donate the cache, so page writes
            # scatter into the pool's own buffer (out_shardings matching
            # the committed input keeps donation exact under a mesh);
            # donation alone does not keep a scatter free of layout
            # copies of the pool (models/cache.kv_token_write)
            self._decode_fn = jax.jit(self._decode_step_paged,
                                      donate_argnums=(1,), **jit_kw)
            self._prefill_fn = jax.jit(self._prefill_paged,
                                       donate_argnums=(1,), **jit_kw)
            self._chunk_fn = jax.jit(self._prefill_chunk_paged,
                                     donate_argnums=(1,), **jit_kw)
            # prefix sharing needs position-faithful, block-addressable
            # KV: pure full-attention archs only
            self.prefix = RadixPrefixIndex(self.pool, block_size) \
                if (prefix_cache and not cfg.ssm_layers
                    and cfg.mla is None and not cfg.sliding_window) \
                else None
        else:
            if mesh is not None:
                raise ValueError(
                    "mesh execution requires the paged KV layout "
                    "(paged=True with an attention arch)")
            self._init_mesh(None, None, kernel_mode)
            self.pool = None
            self.prefix = None
            # slot pool: one batched dense cache over all slots
            self.cache = init_cache(cfg, max_slots, max_seq_len)
            self._decode_fn = jax.jit(self._decode_step)
            self._prefill_fn = jax.jit(self._prefill_one)  # per bucket
            self._chunk_fn = jax.jit(self._prefill_chunk)
        self.chunked_prefill = 0 if cfg.mla is not None else chunked_prefill
        # dense-mode in-progress prefills: slot -> private single-slot
        # cache, committed to the pool when the final chunk completes
        self._partial: Dict[int, object] = {}
        self._warm = set()
        self.cow_copies = 0          # copy-on-write page splits performed
        # warm-path entries (compiles or warm runs while serving) and
        # seconds the host has spent blocked on the device (sync spans)
        self.warm_runs = 0
        self.sync_s = 0.0
        # fused decode+sample dispatch path (serving loop): one jit, one
        # compilation per pow-2 batch width; donation only in paged mode
        # (the dense step merges with `where`, allocating fresh arrays)
        if self.paged:
            self._dispatch_fn = jax.jit(self._decode_dispatch_paged,
                                        donate_argnums=(1,),
                                        **self._jit_kw)
        else:
            self._dispatch_fn = jax.jit(self._decode_dispatch_dense)

    # ------------------------------------------------------------- mesh
    def _init_mesh(self, mesh, parallelism, kernel_mode):
        """Resolve the engine's kernel mode; under a mesh, commit params
        and the paged cache to their NamedShardings and build the
        ``out_shardings`` kwargs the step jits pin outputs with
        (logits/sampled tokens replicated — sampling and the host
        scheduling paths read them — cache on its head-sharded specs).

        The Pallas paged-decode kernel does not partition under GSPMD,
        so a tp > 1 engine declares the pure-jnp gather reference — XLA
        shards that on the kv-head axis automatically (a ``shard_map``
        wrap of the kernel is the follow-up; see docs/sharding.md).
        """
        from repro.kernels import ops
        self.mesh = mesh
        self._jit_kw = {}
        if mesh is None:
            self.kernel_mode = ops.resolve_mode(kernel_mode)
            return self._jit_kw
        from repro.distributed.sharding import (ParallelismConfig,
                                                cache_specs, named,
                                                param_specs)
        par = parallelism if parallelism is not None \
            else ParallelismConfig(fsdp=False)
        self.parallelism = par
        self.params = jax.device_put(
            self.params,
            named(mesh, param_specs(self.params, self.cfg, mesh, par)))
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.cache)
        self._cache_shardings = named(
            mesh, cache_specs(shapes, self.cfg, mesh, par, self.max_slots))
        self._repl = NamedSharding(mesh, P())
        self.cache = jax.device_put(self.cache, self._cache_shardings)
        self.kernel_mode = ops.resolve_mode(kernel_mode,
                                            mesh.shape[par.tp_axis])
        self._jit_kw = {
            "out_shardings": (self._repl, self._cache_shardings)}
        return self._jit_kw

    def replicated(self, x):
        """``x`` committed to the replicated sharding the step jits give
        their logits and sampled tokens (unchanged without a mesh)."""
        if getattr(self, "mesh", None) is None:
            return x
        return jax.device_put(x, self._repl)

    def _commit(self, cache):
        """Re-commit a cache pytree to its shardings.  Host round-trips
        (``_warm_paged`` restore) produce uncommitted single-device
        arrays that would silently violate the jits' donation/sharding
        contract under a mesh; a no-op without one."""
        if getattr(self, "mesh", None) is None:
            return cache
        return jax.device_put(cache, self._cache_shardings)

    # ------------------------------------------------------------- spans
    def _warm_span(self, program: str, length: int):
        """Span and count of one warm-path entry: a compile (or warm
        run) of ``program`` at ``length`` on the serving path."""
        self.warm_runs += 1
        return TraceAnnotation("engine.warm", program=program,
                               length=length)

    @contextlib.contextmanager
    def sync_span(self, name: str = "engine.sync"):
        """Span of a host wait on the device; its seconds add to
        ``sync_s``."""
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name):
                yield
        finally:
            self.sync_s += time.perf_counter() - t0

    # ------------------------------------------------------------ jitted
    def _decode_step(self, params, cache, tokens, active):
        """tokens [B,1]; active [B] bool; returns (logits [B,V], cache)."""
        logits, new_cache = forward_decode(params, self.cfg, tokens=tokens,
                                           cache=new_cache_arg(cache))
        # freeze caches of inactive slots
        def keep(new, old):
            mask = active.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(mask, new, old)
        merged = jax.tree.map(keep, new_cache, cache)
        merged["pos"] = jnp.where(active, new_cache["pos"], cache["pos"])
        return logits[:, -1], merged

    def _prefill_chunk(self, params, cache1, tokens):
        """One chunk continuation over a single-slot cache."""
        return forward_chunk(params, self.cfg, tokens=tokens, cache=cache1)

    def _prefill_one(self, params, tokens, length):
        """tokens [1, Lpad]; length: actual length. Single-slot prefill."""
        cache = init_cache(self.cfg, 1, self.max_seq_len)
        logits, cache, _ = forward_full(params, self.cfg, tokens=tokens,
                                        cache=cache)
        cache["pos"] = jnp.full_like(cache["pos"], length)
        return logits[0, length - 1], cache

    # ------------------------------------------------------- jitted paged
    def _decode_step_paged(self, params, cache, tokens, active):
        """Paged decode round.  KV pages need no inactive-slot freeze:
        freed slots' block tables point at the null page, so their
        (masked) token writes never touch live pages.  Per-slot state
        (pos, SSM conv/ssm) is still frozen."""
        logits, new_cache = forward_decode_paged(
            params, self.cfg, tokens=tokens, cache=new_cache_arg(cache),
            kernel_mode=self.kernel_mode)

        def keep(new, old):
            mask = active.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(mask, new, old)
        layers = []
        for new_l, old_l in zip(new_cache["layers"], cache["layers"]):
            if "conv" in new_l:
                layers.append({k: keep(new_l[k], old_l[k]) for k in new_l})
            else:
                layers.append(new_l)
        return logits[:, -1], {
            "pos": jnp.where(active, new_cache["pos"], cache["pos"]),
            "block_tables": new_cache["block_tables"], "layers": layers}

    def _prefill_paged(self, params, cache, tokens, length, slot):
        """Whole-prompt prefill written in place into ``slot``'s pages."""
        return forward_prefill_paged(params, self.cfg, tokens=tokens,
                                     cache=new_cache_arg(cache), slot=slot,
                                     length=length)

    def _prefill_chunk_paged(self, params, cache, tokens, slot, length):
        """One chunk continuation for ``slot`` against the paged pool.
        ``length`` (traced) marks the valid rows of a padded chunk —
        padded rows route to the null page and are causally masked."""
        return forward_chunk_paged(params, self.cfg, tokens=tokens,
                                   cache=new_cache_arg(cache), slot=slot,
                                   length=length)

    def _warm_paged(self, fn, *args):
        """Compile-warm a donated-cache jitted fn without perturbing
        engine state: snapshot the cache to host, run once, restore."""
        saved = jax.tree.map(np.asarray, self.cache)
        out = fn(self.params, self.cache, *args)
        jax.block_until_ready(out)
        self.cache = self._commit(jax.tree.map(jnp.asarray, saved))

    # --------------------------------------- dispatch/sync split (serving)
    def _slice_slots(self, cache, width):
        """View of the first ``width`` slots of a paged cache: per-slot
        state (pos, block tables, SSM conv/ssm) is sliced, shared page
        arrays pass through untouched."""
        return {
            "pos": cache["pos"][:width],
            "block_tables": cache["block_tables"][:width],
            "layers": [{k: (v[:width] if k in ("conv", "ssm") else v)
                        for k, v in l.items()} for l in cache["layers"]]}

    def _merge_slots(self, cache, sub, width):
        """Scatter a sliced sub-cache back into the full slot pool."""
        layers = []
        for full_l, sub_l in zip(cache["layers"], sub["layers"]):
            layers.append({k: (full_l[k].at[:width].set(sub_l[k])
                               if k in ("conv", "ssm") else sub_l[k])
                           for k in full_l})
        return {"pos": cache["pos"].at[:width].set(sub["pos"]),
                "block_tables": cache["block_tables"], "layers": layers}

    def _decode_dispatch_paged(self, params, cache, tokens, active, key):
        """Fused decode round + on-device sampling over the first
        ``tokens.shape[0]`` slots (a pow-2 batch bucket).  Sampling in
        the same jit means the sampled ids stay on device: the next
        round can be dispatched from them without a host round-trip —
        the core of the serving loop's one-step lookahead."""
        width = tokens.shape[0]
        if width == self.max_slots:
            logits, new_cache = self._decode_step_paged(params, cache,
                                                        tokens, active)
            with jax.named_scope("sample"):
                return sample(logits, key, self.temperature), new_cache
        with jax.named_scope("slots"):
            sub = self._slice_slots(cache, width)
        logits, new_sub = self._decode_step_paged(params, sub, tokens,
                                                  active)
        with jax.named_scope("sample"):
            toks = sample(logits, key, self.temperature)
        with jax.named_scope("slots"):
            return toks, self._merge_slots(cache, new_sub, width)

    def _decode_dispatch_dense(self, params, cache, tokens, active, key):
        """Dense-layout fused decode+sample (always full slot width)."""
        logits, new_cache = self._decode_step(params, cache, tokens, active)
        return sample(logits, key, self.temperature), new_cache

    def dispatch_decode(self, feed, active_np, width: Optional[int] = None,
                        lookahead: int = 0):
        """Dispatch one fused decode+sample round WITHOUT syncing.

        ``feed``: ``[max_slots, 1]`` int32 device array of input token
        ids (each running slot's last sampled token — typically the
        device output of the previous ``dispatch_decode``, so chained
        rounds never touch the host).  ``active_np``: host bool mask.
        ``width``: static batch width (a pow-2 bucket covering every
        active slot; paged mode only) — smaller widths skip the dead
        tail of the slot pool at one extra compile per bucket.
        ``lookahead``: extra write positions the copy-on-write guard
        must cover when earlier rounds are still in flight.

        Returns the device array of sampled next-token ids (``[width]``)
        immediately; the caller reads it back later (``np.asarray``)
        after doing host-side work — scheduling, stream delivery, block
        accounting — while the device computes.
        """
        B = self.max_slots if (width is None or not self.paged) \
            else int(width)
        if not (0 < B <= self.max_slots):
            raise ValueError(f"width {width} outside (0, {self.max_slots}]")
        if any(active_np[B:]):
            raise ValueError(f"active slot >= dispatch width {B}")
        with TraceAnnotation("engine.dispatch", width=B):
            if self.paged:
                self._cow_guard(lookahead)
            if ("dispatch", B) not in self._warm:
                args = (feed[:B], jnp.zeros(B, bool),
                        jax.random.PRNGKey(0))
                with self._warm_span("dispatch", B):
                    if self.paged:
                        self._warm_paged(self._dispatch_fn, *args)
                    else:
                        jax.block_until_ready(self._dispatch_fn(
                            self.params, self.cache, *args))
                self._warm.add(("dispatch", B))
            self.key, sk = jax.random.split(self.key)
            toks, self.cache = self._dispatch_fn(
                self.params, self.cache, feed[:B],
                jnp.asarray(active_np[:B]), sk)
        return toks

    def finish_slot(self, rt: RuntimeRequest):
        """Release a finished request's slot: publish its KV-valid span
        (prompt + all but the never-written final token) to the prefix
        index, return its blocks, and free the slot.  The caller stamps
        phase/finish_time — the serving loop uses wall-clock stamps, the
        batch loop the engine clock."""
        self._index_span(rt, rt.input_len + len(rt.generated) - 1)
        self._release_blocks(rt.slot)
        self.slot_free[rt.slot] = True
        self.slot_req[rt.slot] = None

    # ------------------------------------------------------------ blocks
    def _blocks_needed(self, rt: RuntimeRequest) -> int:
        """Pages covering the request's lifetime token footprint (prompt
        + output budget, capped by the slot's ring length)."""
        tokens = min(rt.input_len + rt.max_new_tokens, self.slot_len)
        return -(-tokens // self.block_size)

    def _prefix_eligible(self, rt: RuntimeRequest) -> bool:
        """Prefix sharing is safe only while the slot ring never wraps:
        a wrap would overwrite aliased pages in place."""
        return (self.prefix is not None
                and rt.input_len + rt.max_new_tokens <= self.slot_len)

    def _probe_cached(self, rt: RuntimeRequest) -> int:
        """Read-only longest-cached-prefix length (tokens) for pricing."""
        if not self._prefix_eligible(rt):
            return 0
        ctx = self._context_tokens(rt)
        return self.prefix.probe(ctx, max_tokens=len(ctx) - 1)

    def _unique_blocks_needed(self, rt: RuntimeRequest) -> int:
        """Blocks the request needs *beyond* the cached prefix it would
        alias — what admission must actually find in the free list."""
        return self._blocks_needed(rt) \
            - self._probe_cached(rt) // self.block_size

    def _admission_blocks(self) -> int:
        """Blocks admission can draw on: the free list plus cached pages
        only the prefix index holds (evictable on demand)."""
        extra = self.prefix.reclaimable() if self.prefix is not None else 0
        return self.pool.available + extra

    def _reserve_blocks(self, rt: RuntimeRequest) -> bool:
        """Atomically reserve the request's block footprint: alias the
        longest cached block-aligned prefix (sharing those pages), evict
        index-only pages if the free list is short, and allocate the
        rest.  The reservation lands in ``rt.block_ids`` /
        ``rt.cached_tokens`` and is consumed by the next prefill.
        Returns False (no state change) when blocks don't cover it."""
        if rt.block_ids is not None:
            return True                      # already reserved this step
        need = self._blocks_needed(rt)
        matched: List[int] = []
        if self._prefix_eligible(rt):
            ctx = self._context_tokens(rt)
            # cap at len-1: the request always writes at least one new
            # token position, so a full-context hit must still leave the
            # final block's frontier in a page this request owns
            matched = self.prefix.match(ctx, max_tokens=len(ctx) - 1)
            self.pool.share(matched)         # pin before any eviction
        n_new = need - len(matched)
        short = n_new - self.pool.available
        if short > 0 and self.prefix is not None:
            self.prefix.evict(short)
        if n_new > self.pool.available:
            self.pool.release(matched)       # roll back the pin
            return False
        rt.block_ids = matched + self.pool.alloc(n_new)
        rt.cached_tokens = len(matched) * self.block_size
        return True

    def _assign_blocks(self, rt: RuntimeRequest, slot: int):
        # upgrade an admission-time reservation: prefills earlier in the
        # same step may have indexed this prompt's prefix since — a
        # re-reservation shares more and allocates strictly less, so it
        # can never fail where the original succeeded
        if rt.block_ids is not None and self._prefix_eligible(rt):
            ctx = self._context_tokens(rt)
            if self.prefix.probe(ctx, max_tokens=len(ctx) - 1) \
                    > rt.cached_tokens:
                self.pool.release(rt.block_ids)
                rt.block_ids = None
                rt.cached_tokens = 0
        if not self._reserve_blocks(rt):
            raise RuntimeError(
                f"out of KV blocks: request {rt.req_id} needs "
                f"{self._unique_blocks_needed(rt)} new blocks, "
                f"{self.pool.available} free")
        ids = rt.block_ids
        rt.block_ids = None                  # reservation consumed
        self._slot_blocks[slot] = ids
        row = np.zeros(self.pages_per_slot, np.int32)
        row[:len(ids)] = ids
        self.cache["block_tables"] = \
            self.cache["block_tables"].at[slot].set(jnp.asarray(row))

    def _release_blocks(self, slot: int):
        if self.paged and self._slot_blocks[slot]:
            self.pool.release(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            self.cache["block_tables"] = \
                self.cache["block_tables"].at[slot].set(0)

    def _index_span(self, rt: RuntimeRequest, n_tokens: int):
        """Publish the slot's first ``n_tokens`` KV positions to the
        prefix index (full blocks only; the index takes its own ref on
        each newly indexed page, so they outlive this request)."""
        if rt.slot < 0 or not self._prefix_eligible(rt):
            return
        ctx = self._context_tokens(rt)
        self.prefix.insert(ctx[:n_tokens], self._slot_blocks[rt.slot],
                           max_tokens=n_tokens)

    def _cow_block(self, slot: int, bi: int) -> int:
        """Give ``slot`` a private copy of its ``bi``-th page (copy-on-
        write) if other owners share it.  Returns the (possibly new)
        page id."""
        old = self._slot_blocks[slot][bi]
        if self.pool.refcount(old) <= 1:
            return old
        if not self.pool.available and self.prefix is not None:
            self.prefix.evict(1)
        new = self.pool.alloc(1)[0]
        self.cache = self._commit(copy_page(self.cache, old, new))
        self._slot_blocks[slot][bi] = new
        self.cache["block_tables"] = \
            self.cache["block_tables"].at[slot, bi].set(new)
        self.pool.release([old])
        self.cow_copies += 1
        return new

    def _cow_guard(self, lookahead: int = 0):
        """Before a decode round writes, split any shared page a slot's
        write frontier sits in.  Block-aligned matching (capped below
        the full context) makes this structurally unreachable through
        normal admission — kept as defense in depth so a shared page
        can never be scribbled on.  ``lookahead`` widens the guard to
        the next write positions when earlier decode rounds are still
        in flight (the serving loop's overlapped dispatch): their host
        token counts lag the device by that many rounds."""
        for slot, rt in enumerate(self.slot_req):
            if rt is None:
                continue
            # a slot mid-prefill writes at its chunk frontier, not at
            # the last-generated position
            pos = rt.prefill_pos if rt.phase is Phase.PREFILLING \
                else rt.input_len + len(rt.generated) - 1
            blocks = self._slot_blocks[slot]
            for d in range(lookahead + 1):
                bi = ((pos + d) % self.slot_len) // self.block_size
                if bi < len(blocks) and \
                        self.pool.refcount(blocks[bi]) > 1:
                    self._cow_block(slot, bi)

    # ------------------------------------------------------------ slots
    def _write_slot(self, slot: int, cache1):
        """Copy a single-request cache into slot ``slot`` of the pool."""
        def put(pool, one):
            return pool.at[slot].set(one[0])
        self.cache["layers"] = [
            {k: put(self.cache["layers"][i][k], cache1["layers"][i][k])
             for k in self.cache["layers"][i]}
            for i in range(len(self.cache["layers"]))]
        self.cache["pos"] = self.cache["pos"].at[slot].set(cache1["pos"][0])

    def free_slots(self) -> List[int]:
        return [i for i, f in enumerate(self.slot_free) if f]

    # ------------------------------------------------------------ steps
    def _context_tokens(self, rt: RuntimeRequest) -> np.ndarray:
        """Prefill context: the prompt, plus — after a preemption — the
        tokens already generated (vLLM-style KV recompute)."""
        if not rt.generated:
            return np.asarray(rt.prompt_tokens, np.int32)
        return np.concatenate([np.asarray(rt.prompt_tokens, np.int32),
                               np.asarray(rt.generated, np.int32)])

    def begin_prefill(self, rt: RuntimeRequest, slot: int):
        """Claim ``slot`` for ``rt`` and stage its prefill — blocks are
        assigned (paged; the cached-prefix span is aliased and skipped)
        and the request enters ``Phase.PREFILLING``, but no compute
        runs.  :meth:`prefill_step` then advances the staged span,
        possibly across several ticks (chunk-as-tick): mid-prefill the
        slot is occupied but the request is invisible to decode rounds
        and to the policies' active view."""
        ctx = self._context_tokens(rt)
        n = len(ctx)
        if n >= self.max_seq_len:
            raise ValueError(f"prefill context {n} >= max_seq_len")
        # block assignment is deferred to the first prefill_step: a
        # prefill completing earlier in the same tick indexes its span,
        # and the assignment-time re-probe then aliases this prompt's
        # cached prefix (the admission reservation in rt.block_ids
        # keeps the pages safe meanwhile)
        rt.prefill_pos = 0
        rt.phase = Phase.PREFILLING
        rt.slot = slot
        if rt.admit_time is None:           # preserved across preemptions
            rt.admit_time = self.clock
        self.slot_free[slot] = False
        self.slot_req[slot] = rt

    def prefill_step(self, rt: RuntimeRequest,
                     length: Optional[int] = None) -> bool:
        """Advance ``rt``'s staged prefill by ``length`` context tokens
        (the whole remaining span when None) — one timed jit call, one
        profiler sample.  The final span samples the first output token
        (its logits sit at the true last context position) and flips
        the request to RUNNING, so it joins the same tick's decode
        round.  Returns True when the prefill completed."""
        with TraceAnnotation("engine.prefill", req=rt.req_id) as span:
            if rt.phase is not Phase.PREFILLING:
                raise ValueError(f"request {rt.req_id} has no staged prefill")
            slot = rt.slot
            if self.paged and not self._slot_blocks[slot]:
                # first step: claim the pages (aliasing any prefix indexed
                # since admission) and skip the cached span — its aliased
                # pages are already populated, so the compute starts
                # mid-sequence
                self._assign_blocks(rt, slot)
                self.cache["pos"] = self.cache["pos"].at[slot].set(
                    rt.cached_tokens)
                rt.prefill_pos = rt.cached_tokens
            ctx = self._context_tokens(rt)
            n = len(ctx)
            done = rt.prefill_pos
            m = n - done if length is None else min(int(length), n - done)
            if m <= 0:
                raise ValueError(
                    f"empty prefill span for request {rt.req_id}")
            span.set_metadata(tokens=m)
            last = done + m >= n
            whole = done == 0 and last
            cache1 = None
            if whole:
                # whole-context fast path: the bucketed prefill jit.  SSM/
                # hybrid states are sequence-order sensitive, so those archs
                # prefill at exact length (one compile per distinct length).
                L = n if self.cfg.ssm_layers else _bucket(n)
                toks = np.zeros((1, L), np.int32)
                toks[0, :n] = ctx
                toks = jnp.asarray(toks)
                # warm the jit cache per bucket so first-seen compile time
                # never pollutes the engine clock / profiler samples
                if ("prefill", L) not in self._warm:
                    with self._warm_span("prefill", L):
                        if self.paged:
                            self._warm_paged(self._prefill_fn, toks, n,
                                             slot)
                        else:
                            self._prefill_fn(self.params, toks,
                                             n)[0].block_until_ready()
                    self._warm.add(("prefill", L))
                t0 = time.perf_counter()
                if self.paged:
                    logits, self.cache = self._prefill_fn(
                        self.params, self.cache, toks, n, slot)
                else:
                    logits, cache1 = self._prefill_fn(self.params, toks, n)
                row = logits[None, :]
            elif self.paged:
                # chunk/suffix continuation against the paged pool: padded
                # to a pow-2 bucket with a traced valid length (padded rows
                # route to the null page and are causally masked), so a
                # ragged final chunk reuses the compiled bucket
                L = _bucket(m)
                toks = np.zeros((1, L), np.int32)
                toks[0, :m] = ctx[done:done + m]
                toks = jnp.asarray(toks)
                if ("chunk", L) not in self._warm:
                    with self._warm_span("chunk", L):
                        self._warm_paged(self._chunk_fn, toks, slot, m)
                    self._warm.add(("chunk", L))
                t0 = time.perf_counter()
                logits, self.cache = self._chunk_fn(self.params, self.cache,
                                                    toks, slot, m)
                row = logits[:, 0]
            else:
                # dense chunk walk over a private single-slot cache (exact
                # length: SSM recurrent state tolerates no pad tokens);
                # committed to the pool only at completion
                if slot not in self._partial:
                    self._partial[slot] = init_cache(self.cfg, 1,
                                                     self.max_seq_len)
                cache1 = self._partial[slot]
                toks = jnp.asarray(np.asarray(ctx[done:done + m],
                                              np.int32)[None])
                if ("chunk", m) not in self._warm:
                    with self._warm_span("chunk", m):
                        self._chunk_fn(self.params, cache1,
                                       toks)[0].block_until_ready()
                    self._warm.add(("chunk", m))
                t0 = time.perf_counter()
                logits, cache1 = self._chunk_fn(self.params, cache1, toks)
                self._partial[slot] = cache1
                row = logits[:, 0]
            with self.sync_span():
                row.block_until_ready()
            dt = time.perf_counter() - t0
            self.clock += dt
            if self.profiler is not None:
                # chunk continuations are prefill work: feed them to the
                # latency-model fit like whole-prompt prefills
                self.profiler.observe_prefill(1, m, dt)
            rt.prefill_pos = done + m
            if not last:
                return False
            if not self.paged:
                self._write_slot(slot, cache1 if whole
                                 else self._partial.pop(slot))
            rt.phase = Phase.RUNNING
            self._index_span(rt, n)
            if rt.ttft_time is None:            # preserved across preemptions
                rt.ttft_time = self.clock
            self.key, sk = jax.random.split(self.key)
            with self.sync_span():
                tok = int(sample(row, sk, self.temperature)[0])
            self._push_token(rt, tok)
            return True

    def prefill(self, rt: RuntimeRequest, slot: int):
        """Whole-prompt prefill: stage the slot and compute the full
        remaining context in one step (any cached prefix aliased).  The
        plan-driven executors instead call :meth:`begin_prefill` once
        and :meth:`prefill_step` per tick, as the discipline's
        :class:`~repro.core.policies.StepPlan` dictates."""
        self.begin_prefill(rt, slot)
        self.prefill_step(rt)

    def preempt(self, rt: RuntimeRequest):
        """Evict a running request: free its slot and discard its KV
        (paged: its blocks return to the pool immediately).  The
        generated tokens and TTFT are kept; the next prefill of this
        request recomputes prompt + generated (cost charged as a normal
        prefill)."""
        if rt.slot < 0 or self.slot_req[rt.slot] is not rt:
            raise ValueError(f"request {rt.req_id} is not running")
        self._partial.pop(rt.slot, None)     # drop any half-built cache
        self._release_blocks(rt.slot)
        self.slot_free[rt.slot] = True
        self.slot_req[rt.slot] = None
        rt.slot = -1
        rt.phase = Phase.WAITING
        rt.prefill_pos = 0
        rt.preemptions += 1

    def _push_token(self, rt: RuntimeRequest, tok: int):
        rt.generated.append(tok)
        if (self.eos >= 0 and tok == self.eos) or \
                len(rt.generated) >= rt.max_new_tokens:
            rt.phase = Phase.FINISHED
            rt.finish_time = self.clock
            self.finish_slot(rt)

    def decode_round(self):
        """One decode iteration over every RUNNING slot.  Slots mid-
        prefill (``Phase.PREFILLING``) are masked out of the batch: in
        paged mode the unmasked page write lands one garbage token at
        their frontier position, which the next prefill chunk overwrites
        before anything reads it (per-slot pos/SSM state *is* frozen by
        the mask)."""
        running = [rt for rt in self.slot_req
                   if rt is not None and rt.phase is Phase.RUNNING]
        active_np = np.array([rt is not None and rt.phase is Phase.RUNNING
                              for rt in self.slot_req])
        if not active_np.any():
            return
        if self.paged:
            self._cow_guard()
        tokens = np.zeros((self.max_slots, 1), np.int32)
        for i, rt in enumerate(self.slot_req):
            if rt is not None and rt.phase is Phase.RUNNING:
                tokens[i, 0] = rt.generated[-1]
        b = int(active_np.sum())
        accum = int(np.max([rt.input_len + len(rt.generated)
                            for rt in running]))
        if "decode" not in self._warm:
            with self._warm_span("decode", self.max_slots):
                if self.paged:
                    self._warm_paged(self._decode_fn, jnp.asarray(tokens),
                                     jnp.asarray(active_np))
                else:
                    self._decode_fn(
                        self.params, self.cache, jnp.asarray(tokens),
                        jnp.asarray(active_np))[0].block_until_ready()
            self._warm.add("decode")
        t0 = time.perf_counter()
        logits, self.cache = self._decode_fn(
            self.params, self.cache, jnp.asarray(tokens),
            jnp.asarray(active_np))
        logits.block_until_ready()
        dt = time.perf_counter() - t0
        self.clock += dt
        if self.profiler is not None:
            self.profiler.observe_decode(b, accum, dt)
        self.key, sk = jax.random.split(self.key)
        toks = np.asarray(sample(logits, sk, self.temperature))
        for i, rt in enumerate(list(self.slot_req)):
            if rt is not None and rt.phase is Phase.RUNNING:
                self._push_token(rt, int(toks[i]))

    # ------------------------------------------------------- step planner
    def plan_step(self, disc: ExecutionDiscipline) -> StepPlan:
        """Build this tick's :class:`StepPlan` from the slot state: one
        prefill item per PREFILLING slot (span sized by the discipline's
        chunk size; ``ref`` is the slot id) and one decode item per
        RUNNING slot."""
        prefills, decodes = [], []
        for slot, rt in enumerate(self.slot_req):
            if rt is None:
                continue
            if rt.phase is Phase.PREFILLING:
                prefills.append((slot, rt.prefill_pos,
                                 rt.input_len + len(rt.generated)))
            elif rt.phase is Phase.RUNNING:
                decodes.append(slot)
        return disc.plan_step(prefills, decodes)

    def execute_step(self, plan: StepPlan):
        """Execute one mixed tick: advance every planned prefill span,
        then run a single decode round over the RUNNING slots — a
        request whose final chunk just completed is activated before
        the round, so its first decode token rides in the same tick
        (chunk-as-tick)."""
        for it in plan.prefills:
            rt = self.slot_req[it.ref]
            if rt is not None and rt.phase is Phase.PREFILLING:
                self.prefill_step(rt, it.length)
        self.decode_round()

    # ------------------------------------------------------------ views
    def active_requests(self) -> List[RuntimeRequest]:
        """RUNNING requests in slot order — the ordering every
        :class:`SchedulerView` built from this engine uses for its
        ``active`` tuple (so ``Decision.preempt`` indices resolve).
        Slots mid-prefill are excluded: they hold no sampled token yet
        and cannot be decoded or preempted."""
        return [rt for rt in self.slot_req
                if rt is not None and rt.phase is Phase.RUNNING]

    def build_view(self, waiting: Sequence[RuntimeRequest],
                   disc: Optional[ExecutionDiscipline],
                   model: Optional[LinearLatencyModel]) -> SchedulerView:
        """:class:`SchedulerView` over the engine's in-flight state plus
        a waiting list — shared by the batch loop (``run_policy``) and
        the streaming serving loop, so policies see identical views in
        both regimes.  ``now`` is the engine clock (the serving loop
        syncs it to the wall clock each tick)."""
        active_rts = self.active_requests()
        b = max(len(active_rts), 1)
        return SchedulerView(
            pending=tuple(rt.request for rt in waiting),
            active=tuple(make_active_view(
                rt.request, len(rt.generated),
                rt.max_new_tokens - len(rt.generated),
                rt.input_len + len(rt.generated), self.clock,
                rt.ttft_time, rt.submit_time, b, model,
                # only pages this request exclusively owns are freeable
                # by preempting it — shared/indexed pages survive its
                # eviction
                blocks_held=(sum(
                    1 for bl in self._slot_blocks[rt.slot]
                    if self.pool.refcount(bl) == 1)
                    if self.paged else 0))
                for rt in active_rts),
            now=self.clock, free=len(self.free_slots()),
            max_batch=self.max_slots,
            pending_generated=tuple(len(rt.generated) for rt in waiting),
            pending_cached=(tuple(self._probe_cached(rt)
                                  for rt in waiting)
                            if self.paged else ()),
            discipline=disc,
            free_blocks=(self._admission_blocks() if self.paged else None),
            total_blocks=(self.pool.total if self.paged else None),
            block_size=(self.block_size if self.paged else 0),
            pages_per_slot=(self.pages_per_slot if self.paged else 0))

    # ------------------------------------------------------------ runs
    def run_policy(self, rts: Sequence[RuntimeRequest],
                   policy: SchedulingPolicy, *,
                   discipline: "ExecutionDiscipline | str | None" = None,
                   model: Optional[LinearLatencyModel] = None,
                   respect_arrivals: bool = False):
        """Continuous batching with a pluggable :class:`SchedulingPolicy`
        — the *same* policy and :class:`ExecutionDiscipline` objects that
        drive the discrete-event core (``repro.core.events.simulate``),
        so simulated and real runs share one scheduling brain.

        The policy sees a :class:`SchedulerView` (pending + active sets,
        slack under ``model`` when provided) and may *preempt* running
        requests; evicted requests lose their KV and are re-prefilled on
        re-admission (prompt + generated tokens).  ``discipline``
        overrides the engine's prefill mode for this run
        (``StallingPrefill`` / ``ChunkedPrefill(n)`` / registry key);
        when omitted, a policy that carries its own discipline
        (dynamic-chunk's ``AdaptiveChunkedPrefill``) runs under it, else
        the engine's ``chunked_prefill`` default applies.  The chosen
        discipline drives the per-tick :class:`StepPlan` — engine config
        is never mutated, so a policy that raises mid-run cannot leave
        the engine reconfigured.  ``respect_arrivals=True`` releases
        each request into the waiting queue only once
        ``Request.arrival_time`` (relative to the run start) has passed
        on the engine clock.
        """
        pol, preemptive = resolve_policy(policy, model=model,
                                         max_batch=self.max_slots)
        if model is None:
            # model-driven policies (slo-reanneal, slo-preempt) carry the
            # latency model the slack projections in the views need
            model = getattr(pol, "model", None)
        if discipline is None:
            # adopt the policy's own discipline: adaptive disciplines
            # (AdaptiveChunkedPrefill) are mutated by their policy
            # mid-run and the planner re-reads chunk_size every tick,
            # so object identity matters (make_discipline passes
            # instances through untouched)
            discipline = getattr(pol, "discipline", None)
        if discipline is not None:
            disc = make_discipline(discipline)
        else:
            disc = ChunkedPrefill(self.chunked_prefill) \
                if self.chunked_prefill else StallingPrefill()
        if disc.chunk_size and self.cfg.mla is not None:
            # MLA archs have no chunked path (see __init__)
            warnings.warn(
                f"{disc!r} is unsupported for MLA archs; falling "
                "back to whole-prompt (stalling) prefill")
            disc = StallingPrefill()
        return self._run_policy_loop(rts, pol, preemptive, model,
                                     respect_arrivals, disc)

    def _run_policy_loop(self, rts, pol, preemptive, model,
                         respect_arrivals, disc):
        rts = list(rts)
        t0 = self.clock
        if respect_arrivals:
            future = sorted(rts, key=lambda rt: rt.request.arrival_time)
            waiting: List[RuntimeRequest] = []
        else:
            future, waiting = [], list(rts)
            for rt in waiting:
                rt.submit_time = self.clock
                rt.request.submit_time = self.clock
        fi = 0
        while waiting or fi < len(future) or not all(self.slot_free):
            # compare on t0 + arrival (not arrival <= clock - t0): the
            # idle-wait below advances the clock to exactly t0 + arrival,
            # and (t0 + a) - t0 can round *below* a, which would leave
            # the request unpulled and the clock pinned — a livelock
            while fi < len(future) and \
                    t0 + future[fi].request.arrival_time <= self.clock:
                rt = future[fi]
                # the true arrival instant (<= self.clock): queueing delay
                # accrued while the engine was mid-step must count toward
                # e2e/TTFT and SLO-budget shifting, as in the event core
                rt.submit_time = t0 + rt.request.arrival_time
                rt.request.submit_time = rt.submit_time
                waiting.append(rt)
                fi += 1
            free = self.free_slots()
            admitted = False
            decided = False
            if waiting and (free or (preemptive
                                     and not all(self.slot_free))):
                view = self.build_view(waiting, disc, model)
                # adaptive disciplines rewrite chunk_size inside
                # decide(); this tick's plan runs under the new size
                admit, preempt = normalize_decision(pol.decide(view), view)
                decided = True
                active_rts = self.active_requests()
                for j in preempt:
                    vict = active_rts[j]
                    # re-prefill must fit: prompt + generated + next token
                    if vict.input_len + len(vict.generated) + 1 \
                            >= self.max_seq_len:
                        continue
                    self.preempt(vict)
                    waiting.append(vict)        # view indices stay valid
                    admitted = True
                free = self.free_slots()
                sel = []
                for j in admit:
                    if len(sel) >= len(free):
                        break
                    # reserve atomically (alias cached prefix + alloc the
                    # unique rest) so same-step admissions never race a
                    # probe against a later allocation
                    if self.paged and not self._reserve_blocks(waiting[j]):
                        continue        # out of KV blocks: keep waiting
                    sel.append(j)
                chosen = [waiting[j] for j in sel]
                for j in sorted(sel, reverse=True):
                    waiting.pop(j)
                for rt, slot in zip(chosen, free):
                    # stage only: the prefill advances through the tick
                    # plans below, chunked or whole per the discipline
                    self.begin_prefill(rt, slot)
                admitted = admitted or bool(chosen)
            retune = getattr(pol, "retune", None)
            if not decided and retune is not None \
                    and not all(self.slot_free):
                # decide() didn't run this tick (empty queue): let an
                # adaptive policy keep resizing its chunk against the
                # current active set, as the event core does
                retune(self.build_view([], disc, model))
            idle = all(self.slot_free)
            self.execute_step(self.plan_step(disc))
            if idle and not admitted:
                if fi < len(future):
                    # idle-wait for the next arrival on the engine clock
                    self.clock = max(self.clock,
                                     t0 + future[fi].request.arrival_time)
                elif waiting:
                    if self.paged and all(
                            self._unique_blocks_needed(rt)
                            > self._admission_blocks()
                            for rt in waiting):
                        rt = waiting[0]
                        raise ValueError(
                            f"request {rt.req_id} needs "
                            f"{self._unique_blocks_needed(rt)} KV blocks "
                            f"but only {self._admission_blocks()} exist: "
                            "prompt + output budget exceeds the block "
                            "pool")
                    raise RuntimeError("admission stalled: policy admitted "
                                       "nothing while the engine was idle")
        return self._collect(rts)

    def run_fcfs(self, rts: Sequence[RuntimeRequest], **kw):
        """Continuous batching, FCFS admission."""
        return self.run_policy(rts, FCFSPolicy(), **kw)

    def run_priority(self, batches: Sequence[Sequence[RuntimeRequest]],
                     **kw):
        """Continuous batching with the planned priority order as arrival
        order — the paper's actual dispatch (§5.1: batches submitted 0.1 ms
        apart into a continuously-batching engine)."""
        return self.run_policy([rt for b in batches for rt in b],
                               FCFSPolicy(), **kw)

    def run_planned(self, batches: Sequence[Sequence[RuntimeRequest]],
                    **kw):
        """Execute scheduler-planned batches sequentially (barrier between
        batches, enforced by ``PlannedPolicy``)."""
        allr = [rt for b in batches for rt in b]
        return self.run_policy(allr, PlannedPolicy(batches), **kw)

    def _collect(self, rts):
        out = {}
        for rt in rts:
            e2e, ttft, tpot = rt.metrics()
            out[rt.req_id] = {
                "e2e": e2e, "ttft": ttft, "tpot": tpot,
                "tokens": list(rt.generated),
                "met": meets_slo(rt.request, e2e, ttft, tpot),
                "preemptions": rt.preemptions,
                "cached": rt.cached_tokens,
            }
        return out

    def prefix_stats(self) -> Dict[str, float]:
        """Prefix-cache counters for benchmarks/diagnostics."""
        if self.prefix is None:
            return {"hit_rate": 0.0, "cached_blocks": 0, "cow_copies": 0,
                    "enabled": False}
        return {"hit_rate": self.prefix.hit_rate,
                "cached_blocks": len(self.prefix),
                "cow_copies": self.cow_copies, "enabled": True}


def new_cache_arg(cache):
    """Shallow rebuild so jit donation aliasing never mutates caller state."""
    out = {"pos": cache["pos"],
           "layers": [dict(l) for l in cache["layers"]]}
    if "block_tables" in cache:
        out["block_tables"] = cache["block_tables"]
    return out
