"""Sharded-serving correctness on a forced 8-device CPU host (run as a
subprocess: the device count is locked at first jax init).

Covers the tentpole acceptance bar end to end:
  1. raw sharded-vs-single logits parity (whole prefill, chunked
     prefill, decode) at <= 1e-5;
  2. the page arrays are *actually* head-sharded (per-device shard is
     1/tp of the kv-head axis) while block tables stay replicated;
  3. engine-level greedy token parity (stall + chunked disciplines),
     with the BlockPool invariants holding throughout and the pool
     draining clean — admission/prefix/CoW never see the mesh;
  4. fleet (N=2 tensor-parallel engines) token parity vs one engine on
     the same seeded trace.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core.slo import SLO, Request  # noqa: E402
from repro.distributed.sharding import (ParallelismConfig, cache_specs,  # noqa: E402
                                        named, param_specs)
from repro.engine.engine import Engine  # noqa: E402
from repro.engine.request import RuntimeRequest  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import ModelConfig, init_params  # noqa: E402
from repro.models.cache import init_paged_cache  # noqa: E402
from repro.models.model import (forward_chunk_paged, forward_decode_paged,  # noqa: E402
                                forward_prefill_paged)

assert jax.local_device_count() == 8, jax.local_device_count()

CFG = ModelConfig(name="verify-tp", family="dense", num_layers=2,
                  d_model=64, num_heads=8, num_kv_heads=8, head_dim=8,
                  d_ff=128, vocab_size=97, dtype="float32")
PARAMS = init_params(jax.random.PRNGKey(0), CFG)
MESH = make_host_mesh()
assert dict(MESH.shape) == {"data": 1, "model": 8}, MESH.shape


def mk_requests(n=6, seed=0, out=8, shared_prefix=0):
    rng = np.random.default_rng(seed)
    pre = rng.integers(1, 96, shared_prefix).tolist() if shared_prefix \
        else []
    rts = []
    for i in range(n):
        toks = pre + rng.integers(1, 96,
                                  int(rng.integers(6, 40))).tolist()
        rts.append(RuntimeRequest(
            request=Request(req_id=i, task_type="chat",
                            input_len=len(toks), slo=SLO(),
                            output_len=out, arrival_time=0.0),
            prompt_tokens=np.asarray(toks, np.int32),
            max_new_tokens=out))
    return rts


# ------------------------------------------------- 1. raw logits parity
def check_logits_parity():
    par = ParallelismConfig(fsdp=False)
    sharded_params = jax.device_put(
        PARAMS, named(MESH, param_specs(PARAMS, CFG, MESH, par)))

    def fresh(shard):
        cache = init_paged_cache(CFG, 4, 128, 33, 16)
        bt = np.zeros((4, 8), np.int32)
        bt[0, :4] = [1, 2, 3, 4]
        cache["block_tables"] = jnp.asarray(bt)
        if not shard:
            return cache, None
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache)
        cs = named(MESH, cache_specs(shapes, CFG, MESH, par, 4))
        return jax.device_put(cache, cs), cs

    toks = jnp.asarray(np.arange(1, 25, dtype=np.int32)[None])
    c1, _ = fresh(False)
    c8, cs = fresh(True)
    repl = NamedSharding(MESH, P())

    lg1, c1 = jax.jit(forward_prefill_paged, static_argnums=(1,))(
        PARAMS, CFG, tokens=toks, cache=c1, slot=0, length=24)
    lg8, c8 = jax.jit(forward_prefill_paged, static_argnums=(1,),
                      out_shardings=(repl, cs))(
        sharded_params, CFG, tokens=toks, cache=c8, slot=0, length=24)
    d = float(np.max(np.abs(np.asarray(lg1) - np.asarray(lg8))))
    assert d <= 1e-5, f"prefill logits diff {d}"

    # the page arrays must be genuinely head-sharded: each device holds
    # 1/8 of the kv heads; the block tables stay fully replicated
    k = c8["layers"][0]["k"]
    shard_shape = k.addressable_shards[0].data.shape
    assert shard_shape[0] * 8 == k.shape[0], (shard_shape, k.shape)
    bt8 = c8["block_tables"]
    assert bt8.addressable_shards[0].data.shape == bt8.shape

    # chunked continuation parity (32-token prompt in two 16 chunks on
    # slot 1 — fresh pages)
    for cache, p, sh in ((c1, PARAMS, None), (c8, sharded_params, cs)):
        bt = np.array(cache["block_tables"])
        bt[1, :4] = [5, 6, 7, 8]
        cache["block_tables"] = jnp.asarray(bt) if sh is None else \
            jax.device_put(jnp.asarray(bt), NamedSharding(MESH, P()))
        cache["pos"] = cache["pos"].at[1].set(0)
    ctx = np.arange(30, 62, dtype=np.int32)
    outs = []
    for cache, p, sh in ((c1, PARAMS, None), (c8, sharded_params, cs)):
        kw = {} if sh is None else {"out_shardings": (repl, sh)}
        fn = jax.jit(forward_chunk_paged, static_argnums=(1,), **kw)
        _, cache = fn(p, CFG, tokens=jnp.asarray(ctx[None, :16]),
                      cache=cache, slot=1, length=16)
        lg, cache = fn(p, CFG, tokens=jnp.asarray(ctx[None, 16:]),
                       cache=cache, slot=1, length=16)
        outs.append((np.asarray(lg), cache))
    d = float(np.max(np.abs(outs[0][0] - outs[1][0])))
    assert d <= 1e-5, f"chunked prefill logits diff {d}"
    c1, c8 = outs[0][1], outs[1][1]

    # decode parity over both occupied slots
    t2 = jnp.asarray(np.array([[24], [61], [0], [0]], np.int32))
    lg1d, _ = jax.jit(forward_decode_paged, static_argnums=(1,))(
        PARAMS, CFG, tokens=t2, cache=c1)
    lg8d, _ = jax.jit(forward_decode_paged, static_argnums=(1,),
                      out_shardings=(repl, cs))(
        sharded_params, CFG, tokens=t2, cache=c8)
    d = float(np.max(np.abs(np.asarray(lg1d) - np.asarray(lg8d))))
    assert d <= 1e-5, f"decode logits diff {d}"
    print(f"logits parity OK (prefill/chunk/decode <= 1e-5)")


# --------------------------------------- 2. engine parity + pool invariants
def pool_ok(eng):
    return eng.pool.available + eng.pool.in_use == eng.pool.total


def check_engine_parity():
    for disc, chunk in (("stall", 0), ("chunked", 16)):
        ref = Engine(CFG, PARAMS, max_slots=4, max_seq_len=128,
                     chunked_prefill=chunk)
        tp = Engine(CFG, PARAMS, max_slots=4, max_seq_len=128,
                    chunked_prefill=chunk, mesh=MESH)
        assert tp.cache["layers"][0]["k"].addressable_shards[0] \
            .data.shape[0] * 8 == CFG.num_kv_heads
        # shared prefix exercises aliasing + CoW under the mesh
        r_ref = ref.run_fcfs(mk_requests(seed=3, shared_prefix=24))
        assert pool_ok(tp)
        r_tp = tp.run_fcfs(mk_requests(seed=3, shared_prefix=24))
        assert pool_ok(tp)
        for k in r_ref:
            assert r_ref[k]["tokens"] == r_tp[k]["tokens"], \
                (disc, k, r_ref[k]["tokens"], r_tp[k]["tokens"])
            assert r_ref[k]["cached"] == r_tp[k]["cached"]
        # drained: every slot free, only prefix-index refs remain
        assert all(tp.slot_free)
        assert tp.pool.in_use == (len(tp.prefix) if tp.prefix else 0)
        print(f"engine token parity OK ({disc}, "
              f"cached={sum(r_tp[k]['cached'] for k in r_tp)}, "
              f"cow={tp.cow_copies})")


def check_cow_under_mesh():
    """Copy-on-write splits a shared frontier page while the cache is
    mesh-sharded: the split (host-side copy_page) must re-commit the
    tree to its shardings and decode identically to the unsharded
    engine.  Manufactured via ``pool.share`` — block-aligned prefix
    matching makes the case unreachable through admission."""
    rng = np.random.default_rng(9)
    toks = rng.integers(1, 96, 20).astype(np.int32)

    def split(mesh):
        eng = Engine(CFG, PARAMS, max_slots=2, max_seq_len=128,
                     mesh=mesh)
        rt = mk_requests(n=1, seed=11)[0]
        rt.prompt_tokens = toks
        rt.request = Request(req_id=0, task_type="chat", input_len=20,
                             slo=SLO(), output_len=4)
        eng.prefill(rt, 0)
        bi = 20 // eng.block_size
        eng.pool.share([eng._slot_blocks[0][bi]])
        eng.decode_round()
        assert eng.cow_copies == 1
        return rt.generated, eng

    g_ref, _ = split(None)
    g_tp, eng = split(MESH)
    assert g_ref == g_tp, (g_ref, g_tp)
    k = eng.cache["layers"][0]["k"]
    assert k.addressable_shards[0].data.shape[0] * 8 == k.shape[0]
    print("copy-on-write page split OK under mesh sharding")


# ----------------------------------------------------- 3. fleet parity
def check_fleet_parity():
    from repro.serving import EngineFleet, ServeLoop
    wl = [(rt.request, rt.prompt_tokens)
          for rt in mk_requests(n=8, seed=5, out=6)]
    single = ServeLoop(Engine(CFG, PARAMS, max_slots=4, max_seq_len=128))
    s_streams = single.submit_trace(
        [(r, t) for r, t in [(rt.request, rt.prompt_tokens)
                             for rt in mk_requests(n=8, seed=5, out=6)]])
    single.serve()
    fleet = EngineFleet(
        [Engine(CFG, PARAMS, max_slots=4, max_seq_len=128, mesh=MESH)
         for _ in range(2)], mapper="least-loaded")
    f_streams = fleet.submit_trace(wl)
    fleet.serve()
    for i, (ss, fs) in enumerate(zip(s_streams, f_streams)):
        assert ss.tokens == fs.tokens, (i, ss.tokens, fs.tokens)
    m = fleet.metrics.summary()
    assert m["n"] == 8
    print(f"fleet (2x tp8 engines) token parity OK, "
          f"tokens={m['tokens']}")


if __name__ == "__main__":
    check_logits_parity()
    check_engine_parity()
    check_cow_under_mesh()
    check_fleet_parity()
    print("ALL OK")
