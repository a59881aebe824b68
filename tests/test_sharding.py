"""Distribution-layer tests on a small host mesh: spec construction for
every architecture, divisibility guards, and a real sharded forward/train
step on an 2x2 virtual-device mesh (process-local)."""
import os

import numpy as np
import pytest

# NOTE: tests run with the default single CPU device; the spec-construction
# tests need no devices, and the sharded-execution tests use a 1x1 mesh.
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED, get_config, get_reduced
from repro.distributed.sharding import (ParallelismConfig, cache_specs,
                                        make_ctx, param_specs)
from repro.models import (forward_decode, forward_full, init_cache,
                          init_params)
from repro.models.cache import cache_spec as cache_sds


def _mesh_1x1():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


@pytest.mark.parametrize("arch", ASSIGNED)
def test_param_specs_structure_matches(arch):
    """Specs pytree has the same structure as params for the FULL config
    (built via eval_shape, no allocation)."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    mesh = _mesh_1x1()
    par = ParallelismConfig()
    specs = param_specs(params, cfg, mesh, par)
    jax.tree.map(lambda a, s: None, params, specs,
                 is_leaf=lambda x: isinstance(x, (P, jax.ShapeDtypeStruct)))


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "dbrx-132b", "mamba2-780m"])
def test_param_specs_divisibility(arch):
    """Every sharded dim is divisible by the mesh axes assigned to it."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    # fake big mesh via devices? use spec math only: build against a
    # synthetic mesh object with the production shape.
    import repro.launch.mesh  # noqa: F401

    class FakeMesh:
        shape = {"data": 16, "model": 16}
    par = ParallelismConfig()
    specs = param_specs(params, cfg, FakeMesh(), par)

    def check(sds, spec):
        if not isinstance(spec, P):
            return
        for dim, ax in zip(sds.shape, tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([FakeMesh.shape[a] for a in axes]))
            assert dim % size == 0, (arch, sds.shape, spec)

    jax.tree.map(check, params, specs,
                 is_leaf=lambda x: isinstance(x, P) or
                 isinstance(x, jax.ShapeDtypeStruct))


def test_cache_specs_prefer_heads_else_seq():
    class FakeMesh:
        shape = {"data": 16, "model": 16}
    cfg = get_config("zamba2-1.2b")      # kv=32 divisible by 16
    shapes = jax.eval_shape(lambda: init_cache(cfg, 128, 1024))
    specs = cache_specs(shapes, cfg, FakeMesh(), ParallelismConfig(), 128)
    attn_layers = [i for i, l in enumerate(shapes["layers"]) if "k" in l]
    assert specs["layers"][attn_layers[0]]["k"][2] == "model"
    cfg2 = get_config("qwen3-1.7b")      # kv=8 -> seq sharding
    shapes2 = jax.eval_shape(lambda: init_cache(cfg2, 128, 1024))
    specs2 = cache_specs(shapes2, cfg2, FakeMesh(), ParallelismConfig(), 128)
    assert specs2["layers"][0]["k"][1] == "model"
    assert specs2["layers"][0]["k"][2] is None


def test_sharded_forward_runs_on_mesh():
    """jit with NamedShardings on a 1x1 mesh executes and matches the
    unsharded forward bit-for-bit."""
    import dataclasses
    cfg = dataclasses.replace(get_reduced("qwen3-1.7b"), dtype="float32")
    mesh = _mesh_1x1()
    par = ParallelismConfig()
    params = init_params(jax.random.PRNGKey(0), cfg)
    specs = param_specs(params, cfg, mesh, par)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                       is_leaf=lambda x: isinstance(x, P))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    ctx = make_ctx(mesh, par)

    def fn(p, t):
        logits, _, _ = forward_full(p, cfg, tokens=t, ctx=ctx)
        return logits

    sharded = jax.jit(fn, in_shardings=(psh, NamedSharding(mesh, P())))(
        params, toks)
    plain = fn(params, toks)
    np.testing.assert_allclose(np.asarray(sharded, np.float32),
                               np.asarray(plain, np.float32), atol=2e-2,
                               rtol=2e-2)


def _paged_cfg(kv_heads=8):
    from repro.models import ModelConfig
    return ModelConfig(name="t-paged", family="dense", num_layers=2,
                       d_model=8 * kv_heads, num_heads=kv_heads,
                       num_kv_heads=kv_heads, head_dim=8,
                       d_ff=32, vocab_size=97, dtype="float32")


def test_paged_cache_specs_head_sharding():
    """Paged layout: page arrays shard the kv-head axis when divisible,
    block tables / pos stay replicated (they are host bookkeeping)."""
    from repro.models.cache import init_paged_cache

    class FakeMesh:
        shape = {"data": 1, "model": 2}
    cfg = _paged_cfg(kv_heads=8)
    shapes = jax.eval_shape(lambda: init_paged_cache(cfg, 4, 128, 33, 16))
    specs = cache_specs(shapes, cfg, FakeMesh(), ParallelismConfig(), 4)
    assert specs["block_tables"] == P(None, None)
    assert specs["pos"] == P(None)
    for lyr in specs["layers"]:
        # [kv_heads, num_blocks, page, head_dim] — heads on "model"
        assert lyr["k"] == P("model", None, None, None)
        assert lyr["v"] == P("model", None, None, None)


def test_paged_cache_specs_indivisible_heads_replicate():
    """kv heads not divisible by the tp axis -> pages replicate rather
    than shard unevenly (never seq-shard pages: a page is a time slab,
    every shard needs all of it)."""
    from repro.models.cache import init_paged_cache

    class FakeMesh:
        shape = {"data": 1, "model": 2}
    cfg = _paged_cfg(kv_heads=3)
    shapes = jax.eval_shape(lambda: init_paged_cache(cfg, 4, 128, 33, 16))
    specs = cache_specs(shapes, cfg, FakeMesh(), ParallelismConfig(), 4)
    for lyr in specs["layers"]:
        assert lyr["k"] == P(None, None, None, None)


def test_make_host_mesh_sizing():
    """make_host_mesh spans whatever the host exposes: tp defaults to
    local_device_count // data, explicit tp is honored."""
    from repro.launch.mesh import make_host_mesh
    n = jax.local_device_count()
    mesh = make_host_mesh()
    assert dict(mesh.shape) == {"data": 1, "model": n}
    mesh = make_host_mesh(tp=1)
    assert dict(mesh.shape) == {"data": 1, "model": 1}


# ------------------------------------------------- instance mappers
def _req(i, task="chat", l_in=32, l_out=16):
    from repro.core.slo import SLO, Request
    return Request(req_id=i, task_type=task, input_len=l_in, slo=SLO(),
                   output_len=l_out)


def _states(n, **kw):
    from repro.core.policies import InstanceState
    return [InstanceState(instance_id=i, **{k: v[i] for k, v in
                                            kw.items()})
            for i in range(n)]


def test_mapper_round_robin_and_least_loaded():
    from repro.core.policies import make_mapper
    rr = make_mapper("round-robin")
    assert rr.map_batch([_req(i) for i in range(5)], _states(2)) == \
        [0, 1, 0, 1, 0]
    assert rr.map_one(_req(5), _states(2)) == 1     # cursor persists
    ll = make_mapper("least-loaded")
    st = _states(3, queue_depth=[4, 0, 1], active=[0, 2, 0])
    # loads 4/2/1 -> first goes to 2, then 1 and 2 tie -> lowest id
    assert ll.map_batch([_req(0), _req(1), _req(2)], st) == [2, 1, 2]


def test_mapper_slo_affinity_homes_classes():
    from repro.core.policies import make_mapper
    m = make_mapper("slo-affinity")
    reqs = [_req(0, "chat"), _req(1, "code"), _req(2, "chat"),
            _req(3, "summ"), _req(4, "code")]
    out = m.map_batch(reqs, _states(2))
    assert out == [0, 1, 0, 0, 1]    # chat->0, code->1, summ wraps to 0


def test_memory_greedy_matches_eq20_reference():
    """Regression: the shared mapper reproduces the inline Eq. 20 loop
    that SLOAwareScheduler.assign_instances used to carry."""
    from repro.core.policies import MemoryGreedyMapper
    from repro.core.profiler import MemoryModel
    mem = MemoryModel(total_memory=200.0, mu=0.9, sigma_per_token=1.0)
    rng = np.random.default_rng(0)
    reqs = [_req(i, l_in=int(rng.integers(8, 80)),
                 l_out=int(rng.integers(8, 40))) for i in range(40)]
    got = MemoryGreedyMapper(mem).map_batch(reqs, _states(3))

    remaining = [mem.total] * 3                     # inline reference
    want = []
    for r in reqs:
        need = mem.tokens_to_memory(r.input_len + r.planning_output_len())
        tgt = int(np.argmax(remaining))
        if remaining[tgt] < need:
            remaining = [mem.total] * 3
            tgt = 0
        remaining[tgt] -= need
        want.append(tgt)
    assert got == want
    assert len(set(got)) == 3                       # all instances used


def test_scheduler_assign_instances_delegates_to_mapper():
    from repro.core import PAPER_TABLE2
    from repro.core.policies import MemoryGreedyMapper
    from repro.core.profiler import MemoryModel
    from repro.core.scheduler import SLOAwareScheduler
    mem = MemoryModel(total_memory=500.0)
    sched = SLOAwareScheduler(PAPER_TABLE2, num_instances=2, memory=mem)
    reqs = [_req(i, l_in=16 + 13 * i) for i in range(9)]
    buckets = sched.assign_instances(reqs)
    flat = MemoryGreedyMapper(mem).map_batch(reqs, _states(2))
    for inst in range(2):
        assert [r.req_id for r in buckets[inst]] == \
            [r.req_id for r, a in zip(reqs, flat) if a == inst]


def test_mapper_plan_preserves_order():
    """The default plan groups map_batch output without reordering —
    the fleet submits each instance's queue in arrival order."""
    from repro.core.policies import make_mapper
    m = make_mapper("least-loaded")
    reqs = [_req(i) for i in range(7)]
    plan = m.plan(reqs, _states(2))
    assert sorted(i for q in plan for i in q) == list(range(7))
    for q in plan:
        assert q == sorted(q)


def test_mapper_annealed_plan_covers_all():
    from repro.core import PAPER_TABLE2, SAParams
    from repro.core.policies import make_mapper
    m = make_mapper("annealed", model=PAPER_TABLE2, max_batch=4,
                    sa_params=SAParams(iters=40, seed=0))
    reqs = [_req(i, l_in=16 + 9 * i) for i in range(10)]
    plan = m.plan(reqs, _states(2))
    assert sorted(i for q in plan for i in q) == list(range(10))


# ------------------------------------------------- fleet (single device)
def test_fleet_token_parity_single_device():
    """A 2-engine fleet produces the same greedy tokens as one loop on
    the same backlogged trace (no mesh: plain engines, tier-1 safe)."""
    from repro.engine.engine import Engine
    from repro.serving import EngineFleet, ServeLoop

    cfg = _paged_cfg(kv_heads=2)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    pairs = [(_req(i, l_in=n, l_out=4),
              rng.integers(1, 96, n).astype(np.int32))
             for i, n in enumerate(rng.integers(6, 24, 6).tolist())]

    def run(target):
        streams = target.submit_trace([(r, t) for r, t in pairs])
        target.serve()
        return [s.tokens for s in streams]

    single = run(ServeLoop(Engine(cfg, params, max_slots=2,
                                  max_seq_len=64)))
    fleet = EngineFleet([Engine(cfg, params, max_slots=2, max_seq_len=64)
                         for _ in range(2)], mapper="round-robin")
    assert run(fleet) == single
    m = fleet.metrics.summary()
    assert m["n"] == 6 and m["tokens"] == 24


@pytest.mark.slow
def test_sharded_serving_multidevice():
    """Full sharded-serving verification on a forced 8-device CPU host
    (subprocess: the device count is locked at first jax init).  Covers
    sharded-vs-single logits parity <= 1e-5 (prefill / chunked /
    decode), real head-sharded page placement, engine + fleet token
    parity, pool invariants and CoW under the mesh."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable,
         os.path.join(root, "tests", "helpers", "verify_sharding.py")],
        env=dict(os.environ, PYTHONPATH=os.path.join(root, "src")),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALL OK" in out.stdout


def test_kv_writes_stay_local_on_head_sharded_pool():
    """Token and prefill-span K/V writes on a pool head-sharded as the
    engine's mesh places it (forced 4-device CPU host, subprocess): no
    collective, the pool's sharding kept, the head-window scatter's
    values off the null page."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable,
         os.path.join(root, "tests", "helpers", "verify_kv_write_mesh.py")],
        env=dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
                 JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALL OK" in out.stdout


def test_moe_shard_map_matches_local():
    """MoE FFN with a mesh ctx == MoE FFN without (1x1 mesh)."""
    from repro.models.moe import ShardingCtx, init_moe, moe_ffn
    cfg = get_reduced("deepseek-v2-lite-16b")
    mesh = _mesh_1x1()
    block = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32)
    out_local, aux_local = moe_ffn(block, cfg, x, None)
    ctx = ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis="model")
    out_sm, aux_sm = jax.jit(lambda b, xx: moe_ffn(b, cfg, xx, ctx))(block, x)
    np.testing.assert_allclose(np.asarray(out_sm), np.asarray(out_local),
                               atol=1e-5, rtol=1e-5)
    assert abs(float(aux_sm) - float(aux_local)) < 1e-5
