"""K/V pool writes under a head-sharded mesh, on a forced 4-device CPU
host (run as a subprocess: the device count is locked at first jax init).

The pool is placed as ``Engine._init_mesh`` places it (``cache_specs``:
kv-head axis on ``"model"``), the new rows arrive head-sharded (as the
head-sharded ``wk``/``wv`` projections give them) or replicated.  Every
compiled token and prefill-span write must hold no collective, return
the pool on its own sharding, and store what the head-window scatter
``pages.at[:, page_ids, offs].set`` stores off the null page.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import re  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.distributed.sharding import (ParallelismConfig, cache_specs,  # noqa: E402
                                        named)
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import ModelConfig  # noqa: E402
from repro.models.cache import (NULL_PAGE, _prefill_targets,  # noqa: E402
                                init_paged_cache, kv_prefill_write,
                                kv_token_write)

assert jax.local_device_count() == 4, jax.local_device_count()
MESH = make_host_mesh()
CFG = ModelConfig(name="verify-kv-write", family="dense", num_layers=1,
                  d_model=64, num_heads=8, num_kv_heads=4, head_dim=8,
                  d_ff=128, vocab_size=97, dtype="float32")
COLLECTIVE = re.compile(r"\b(all-gather|all-reduce|all-to-all|"
                        r"collective-permute|reduce-scatter)")


def pool_sharding():
    shapes = jax.eval_shape(lambda: init_paged_cache(CFG, 4, 64, 9, 4))
    specs = cache_specs(shapes, CFG, MESH, ParallelismConfig(fsdp=False), 4)
    return named(MESH, specs)["layers"][0]["k"]


def main():
    sh = pool_sharding()
    assert sh.spec == P("model", None, None, None), sh.spec
    kv, n_blocks, page, hd = 4, 9, 4, 8
    rows = 6
    pool = jax.random.normal(jax.random.PRNGKey(0),
                             (kv, n_blocks, page, hd), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (rows, kv, hd),
                          jnp.float32)
    page_ids = jnp.asarray([3, 0, 0, 5, 8, 1], jnp.int32)
    offs = jnp.asarray([1, 2, 2, 0, 3, 3], jnp.int32)
    bt = jnp.asarray([2, 6, 4], jnp.int32)
    span = _prefill_targets(bt, rows, page, 5, 9)   # wraps the 12-row ring
    writes = {
        "token": (lambda p, v: kv_token_write(p, v, page_ids, offs),
                  (page_ids, offs)),
        "prefill": (lambda p, v: kv_prefill_write(p, v, bt, 5, 9), span),
    }
    live = np.arange(n_blocks) != NULL_PAGE
    for vals_spec in (P(None, "model", None), P()):
        vals = jax.device_put(x, NamedSharding(MESH, vals_spec))
        for name, (write, (ids, o)) in writes.items():
            fn = jax.jit(write, donate_argnums=(0,), out_shardings=sh)
            hlo = fn.lower(jax.device_put(pool, sh), vals).compile().as_text()
            found = sorted(set(COLLECTIVE.findall(hlo)))
            assert not found, (name, vals_spec, found)
            got = fn(jax.device_put(pool, sh), vals)
            assert got.sharding.is_equivalent_to(sh, got.ndim), \
                (name, vals_spec, got.sharding)
            want = pool.at[:, ids, o].set(jnp.swapaxes(x, 0, 1))
            np.testing.assert_array_equal(np.asarray(got)[:, live],
                                          np.asarray(want)[:, live])
            print(f"{name} vals {vals_spec}: no collective, pool sharding "
                  f"kept, equal off the null page")
    print("ALL OK")


if __name__ == "__main__":
    main()
