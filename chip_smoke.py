#!/usr/bin/env python3
"""Chip smoke test: serve qwen3-1.7b at full width on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the multi-chip phase only

One chip: the paged-decode kernel mode must resolve to ``pallas``; one
full-width decode step through the compiled paged kernel is compared
with the jnp gather reference; the latency model is fitted on the chip;
then eight requests are served through ``ServeLoop`` under
``slo-reanneal`` and each must complete with its full token budget.

``--chips 4``: qwen3-1.7b on a tp=4 mesh against a tp=1 engine on one of
the same chips (prefill logits and the first decode steps), then a
4-replica ``EngineFleet``, one replica per chip, serving a short trace.

Weights are random, made from ``--seed``.  The script is one process and
starts no other.  It exits non-zero, with no result line, when JAX finds
no TPU or any phase fails; otherwise its last line is the JSON result
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Compile
seconds (XLA backend compiles, summed over the process) are printed
apart from run seconds; the persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache`` here.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen3-1.7b"
KERNEL_MODE = "pallas"          # what the paged decode must resolve to
MAX_SEQ_LEN = 256
N_REQUESTS = 8                  # served by each phase, one chip or four
BLOCK_SIZE = 16
# bf16 tolerance of the kernel-vs-reference attention output (the bound
# tests/test_kernels.py holds bf16 kernels to): |d| <= ATOL + RTOL*|ref|
ATOL = RTOL = 2e-2
# decode-step and tp=4 logits: max |d| within this share of max |ref|.
# bf16 rounding at different points (kernel vs reference, sharded vs
# whole matmuls) drifts ~2e-2 over 28 layers; a wrong page, mask or
# head mapping moves the logits by O(1).
LOGITS_REL_TOL = 0.1


class CompileClock:
    """Sums XLA backend compile seconds and persistent-cache hits."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        self._compile_event = BACKEND_COMPILE_EVENT

    def _on_duration(self, event, secs, **_):
        if event == self._compile_event:
            self.seconds += secs
            self.count += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phase:
    """Times one phase: wall seconds and the XLA compile seconds inside
    it (summed over threads, so a fleet's concurrent compiles can add
    up to more than the wall time)."""

    def __init__(self, name, clock):
        self.name, self.clock = name, clock

    def __enter__(self):
        print(f"== {self.name}", flush=True)
        self.t0 = time.perf_counter()
        self.c0, self.n0 = self.clock.seconds, self.clock.count
        return self

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        print(f"   {self.name}: compile_s="
              f"{self.clock.seconds - self.c0:.2f} "
              f"({self.clock.count - self.n0} compiles) "
              f"wall_s={time.perf_counter() - self.t0:.2f}", flush=True)
        return False


def check(ok, what):
    """Fail the smoke (a raise, which ``python -O`` keeps, unlike
    ``assert``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def rel_diff(got, want):
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def prefill_prompts(params, cfg, cache, prompts):
    """Prefill each prompt into its slot of a paged cache (one padded
    prefill compile); returns (last-token logits [B, V], cache)."""
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.model import forward_prefill_paged
    fn = jax.jit(functools.partial(forward_prefill_paged, cfg=cfg))
    rows = []
    for slot, p in enumerate(prompts):
        toks = np.zeros((1, MAX_SEQ_LEN), np.int32)
        toks[0, :len(p)] = p
        logits, cache = fn(params, tokens=jnp.asarray(toks), cache=cache,
                           slot=slot, length=len(p))
        rows.append(logits)
    return jnp.stack(rows), cache


def identity_tables(cache, n_slots):
    import jax.numpy as jnp
    npg = cache["block_tables"].shape[1]
    cache["block_tables"] = jnp.arange(
        1, 1 + n_slots * npg, dtype=jnp.int32).reshape(n_slots, npg)
    return cache


# ------------------------------------------------------------- one chip
def kernel_parity(cfg, params, rng, clock):
    """One full-width decode step: compiled paged kernel vs reference."""
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.models.cache import init_paged_cache
    from repro.models.model import forward_decode_paged
    lens = [23, 77, 130, 200]                   # partial and full pages
    B = len(lens)
    npg = MAX_SEQ_LEN // BLOCK_SIZE
    with Phase("kernel parity (paged decode, full width)", clock):
        cache = identity_tables(init_paged_cache(
            cfg, B, MAX_SEQ_LEN, 1 + B * npg, BLOCK_SIZE), B)
        prompts = [rng.integers(0, cfg.vocab_size, n) for n in lens]
        _, cache = prefill_prompts(params, cfg, cache, prompts)
        # the kernel alone, on the prefilled layer-0 pages
        layer = cache["layers"][0]
        q = jax.random.normal(jax.random.PRNGKey(1),
                              (B, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
        lengths = jnp.asarray(lens, jnp.int32)
        outs = {m: np.asarray(ops.decode_attention_paged(
            q, layer["k"], layer["v"], cache["block_tables"], lengths,
            mode=m), np.float32) for m in (KERNEL_MODE, "ref")}
        d = np.abs(outs[KERNEL_MODE] - outs["ref"])
        bound = ATOL + RTOL * np.abs(outs["ref"])
        print(f"   attention out: max|kernel-ref|={d.max():.3e} "
              f"(bf16 tolerance {ATOL:g} + {RTOL:g}*|ref|, "
              f"worst margin {float((d - bound).max()):.3e})")
        check(np.all(d <= bound), "paged kernel differs from reference")
        # one whole decode step through the model, both modes
        nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)
        logits = {}
        for m in (KERNEL_MODE, "ref"):
            step = jax.jit(functools.partial(forward_decode_paged, cfg=cfg,
                                             kernel_mode=m))
            logits[m] = np.asarray(step(params, tokens=nxt,
                                        cache=cache)[0][:, -1], np.float32)
        rel = rel_diff(logits[KERNEL_MODE], logits["ref"])
        agree = float(np.mean(logits[KERNEL_MODE].argmax(-1)
                              == logits["ref"].argmax(-1)))
        print(f"   decode step logits: max|kernel-ref|/max|ref|={rel:.3e} "
              f"(tolerance {LOGITS_REL_TOL:g}), argmax agreement "
              f"{agree:.2f}")
        check(rel <= LOGITS_REL_TOL, "decode-step logits differ")


def serve_one_chip(cfg, params, rng, clock):
    from repro.data.synthetic import sample_serve_workload
    from repro.engine.engine import Engine
    from repro.launch.serve import fit_latency_model
    from repro.serving import ServeLoop
    max_batch = 4
    with Phase("latency model fit (on the chip)", clock):
        model = fit_latency_model(cfg, params, max_batch, rng)
        print("   fitted (s): " + " ".join(
            f"{k}={float(v):.4g}" for k, v in vars(model).items()))
    with Phase(f"serve {N_REQUESTS} requests (ServeLoop, slo-reanneal)",
               clock):
        eng = Engine(cfg, params, max_slots=max_batch,
                     max_seq_len=MAX_SEQ_LEN, block_size=BLOCK_SIZE)
        print(f"   engine kernel_mode={eng.kernel_mode}")
        check(eng.kernel_mode == KERNEL_MODE,
              f"kernel_mode {eng.kernel_mode}, want {KERNEL_MODE}")
        pairs = sample_serve_workload(N_REQUESTS, cfg.vocab_size, rng=rng,
                                      arrival_rate=4.0)
        loop = ServeLoop(eng, "slo-reanneal", model=model)
        warm_and_serve(loop, [eng], pairs, clock)


def warm_and_serve(loop, engines, pairs, clock):
    """Compile-warm a ServeLoop or EngineFleet over ``engines``, serve
    ``pairs``, check every request got its full budget and no engine
    compiled a decode step while serving, print the metrics summary."""
    t, c = time.perf_counter(), clock.seconds
    loop.start(warm_lengths=[len(p) for _, p in pairs])
    print(f"   warm-up: wall_s={time.perf_counter() - t:.2f} "
          f"compile_s={clock.seconds - c:.2f}")
    warmed = [eng._dispatch_fn._cache_size() for eng in engines]
    streams = loop.submit_trace(pairs)
    t, c = time.perf_counter(), clock.seconds
    loop.serve()
    print(f"   serve: wall_s={time.perf_counter() - t:.2f} "
          f"compile_s={clock.seconds - c:.2f}")
    check_streams(pairs, streams)
    served = [eng._dispatch_fn._cache_size() for eng in engines]
    print(f"   decode-step executables per engine: after warm-up {warmed}, "
          f"after serving {served}")
    check(served == warmed, "an engine compiled a decode step while serving")
    summary = loop.metrics.summary()
    print("   smoke reading, not a benchmark: "
          + json.dumps({k: summary[k] for k in sorted(summary)}))


def check_streams(pairs, streams):
    ok = True
    for (req, prompt), s in zip(pairs, streams):
        full = len(s.tokens) == req.output_len
        ok &= full and s.done and s.error is None
        print(f"   request {s.req_id}: prompt_len={len(prompt)} "
              f"generated={len(s.tokens)}/{req.output_len} "
              f"completed={s.done and s.error is None and full}")
    check(ok, "a request did not complete with its full token budget")


# ---------------------------------------------------------- four chips
def tp_parity(cfg, params, rng, clock, devs):
    """tp=4 mesh engine vs a tp=1 engine on one of the same chips."""
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.engine.engine import Engine
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import forward_decode_paged
    lens = [40, 150]
    B = len(lens)
    prompts = [rng.integers(0, cfg.vocab_size, n) for n in lens]
    kw = dict(max_slots=B, max_seq_len=MAX_SEQ_LEN, block_size=BLOCK_SIZE)
    with Phase("tp=4 vs tp=1 logits parity", clock):
        engines = {
            "tp1": Engine(cfg, params, mesh=make_host_mesh(
                tp=1, devices=devs[:1]), **kw),
            "tp4": Engine(cfg, params, mesh=make_host_mesh(
                tp=4, devices=devs[:4]), **kw)}
        got, caches, steps = {}, {}, {}
        for name, eng in engines.items():
            print(f"   {name}: mesh={dict(eng.mesh.shape)} "
                  f"kernel_mode={eng.kernel_mode}")
            pre, caches[name] = prefill_prompts(
                eng.params, cfg, identity_tables(eng.cache, B), prompts)
            got[name] = [np.asarray(pre, np.float32)]
            steps[name] = jax.jit(functools.partial(
                forward_decode_paged, cfg=cfg, kernel_mode=eng.kernel_mode))
        for _ in range(3):      # both fed the tp=1 engine's greedy tokens
            nxt = jnp.asarray(got["tp1"][-1].argmax(-1)[:, None], jnp.int32)
            for name, eng in engines.items():
                lg, caches[name] = steps[name](eng.params, tokens=nxt,
                                               cache=caches[name])
                got[name].append(np.asarray(lg[:, -1], np.float32))
        for i, (a, b) in enumerate(zip(got["tp4"], got["tp1"])):
            rel = rel_diff(a, b)
            what = "prefill" if i == 0 else f"decode step {i}"
            print(f"   {what}: max|tp4-tp1|/max|tp1|={rel:.3e} "
                  f"(tolerance {LOGITS_REL_TOL:g}) argmax agreement "
                  f"{float(np.mean(a.argmax(-1) == b.argmax(-1))):.2f}")
            check(rel <= LOGITS_REL_TOL, f"tp=4 {what} logits differ")


def fleet_four_chips(cfg, params, rng, clock):
    import jax
    from repro.data.synthetic import sample_serve_workload
    from repro.launch.serve import make_fleet_engines
    from repro.serving import EngineFleet
    with Phase(f"4-replica fleet, {N_REQUESTS} requests", clock):
        engines = make_fleet_engines(cfg, params, 4, max_slots=2,
                                     max_seq_len=MAX_SEQ_LEN,
                                     block_size=BLOCK_SIZE)
        homes = []
        for i, eng in enumerate(engines):
            devs = {d for leaf in jax.tree.leaves((eng.params, eng.cache))
                    for d in leaf.devices()}
            check(len(devs) == 1, f"replica {i} spans {devs}")
            homes.append(devs.pop())
            print(f"   replica {i}: device {homes[-1].id} "
                  f"kernel_mode={eng.kernel_mode}")
        check(len(set(homes)) == 4, f"replicas share devices: {homes}")
        # prompts in one prefill bucket (64): fewer per-replica compiles
        pairs = sample_serve_workload(N_REQUESTS, cfg.vocab_size, rng=rng,
                                      arrival_rate=8.0, in_range=(33, 64),
                                      out_range=(8, 24))
        warm_and_serve(EngineFleet(engines, "fcfs", mapper="round-robin"),
                       engines, pairs, clock)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import numpy as np
    from repro.configs import get_config
    from repro.launch.compile_cache import setup_compile_cache
    from repro.models import init_params

    cache_dir = setup_compile_cache()
    clock = CompileClock(jax)
    print(f"device_kind={devs[0].device_kind} device_count={len(devs)} "
          f"jax={jax.__version__} compile_cache={cache_dir}")
    cfg = get_config(ARCH)
    rng = np.random.default_rng(args.seed)
    with Phase(f"init {ARCH} params (seed {args.seed})", clock):
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
        n = sum(x.size for x in jax.tree.leaves(params))
        nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
        print(f"   {ARCH}: {cfg.num_layers} layers d_model={cfg.d_model} "
              f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
              f"params={n / 1e9:.3f}B ({nbytes / 2**30:.2f} GiB {cfg.dtype})")
    if args.chips == 1:
        kernel_parity(cfg, params, rng, clock)
        serve_one_chip(cfg, params, rng, clock)
    else:
        tp_parity(cfg, params, rng, clock, devs)
        fleet_four_chips(cfg, params, rng, clock)
    print(f"compile_s_total={clock.seconds:.2f} compiles={clock.count} "
          f"persistent_cache_hits={clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
