import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.models.moe import ShardingCtx, init_moe, moe_ffn, _local_moe
from repro.models.config import ModelConfig, MoEConfig

cfg = ModelConfig(name="m", family="moe", num_layers=1, d_model=64,
                  num_heads=4, num_kv_heads=4, d_ff=0, vocab_size=97, dtype="float32",
                  moe=MoEConfig(num_experts=8, top_k=2, d_ff_expert=96))
params = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64), jnp.float32)
out_ref, aux_ref = moe_ffn(params, cfg, x, None)
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
ctx = ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis="model", expert_parallel=True)
out_ep, aux_ep = jax.jit(lambda p, xx: moe_ffn(p, cfg, xx, ctx))(params, x)
err = float(jnp.max(jnp.abs(out_ep - out_ref)))
print("EP vs local max err:", err, "aux", float(aux_ep), float(aux_ref))
assert err < 1e-4
# gather-baseline path too
ctx2 = ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis="model", expert_parallel=False)
out_g, _ = jax.jit(lambda p, xx: moe_ffn(p, cfg, xx, ctx2))(params, x)
err2 = float(jnp.max(jnp.abs(out_g - out_ref)))
print("gather vs local max err:", err2)
assert err2 < 1e-4

# 2D expert-parallel (decode-style small token count, fsdp ff sharding)
ctx4 = ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis="model",
                   expert_parallel=True, fsdp_axes=("data",))
out_2d, _ = jax.jit(lambda p, xx: moe_ffn(p, cfg, xx, ctx4))(params, x)
err4 = float(jnp.max(jnp.abs(out_2d - out_ref)))
print("EP-2D vs local max err:", err4)
assert err4 < 1e-4
# seq-parallel attention correctness on a multi-device mesh
from repro.models import init_params, forward_full
from repro.models.config import ModelConfig as MC
dcfg = MC(name="d", family="dense", num_layers=2, d_model=64, num_heads=6,
          num_kv_heads=2, d_ff=128, vocab_size=97, dtype="float32")
dp = init_params(jax.random.PRNGKey(2), dcfg)
toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, 97)
ref, _, _ = forward_full(dp, dcfg, tokens=toks)
ctx3 = ShardingCtx(mesh=mesh, dp_axes=("data",), tp_axis="model", attn_sharding="auto")
got, _, _ = jax.jit(lambda p, t: forward_full(p, dcfg, tokens=t, ctx=ctx3)[:2])(dp, toks)[0], None, None
err3 = float(jnp.max(jnp.abs(got - ref)))
print("seq-par attn vs local max err:", err3)
assert err3 < 1e-3
print("ALL OK")
