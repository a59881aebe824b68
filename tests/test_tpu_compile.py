"""TPU v5e compile rehearsals: the served path's Pallas kernels at real
widths, compiled for a described (not attached) v5e chip.  Nothing runs;
a compile that passes shows only that the chip's compiler accepts the
kernel (block tiling, VMEM scratch layout), which interpret-mode tests
cannot show.  The topology is described inside a fixture so that no
module import loads the TPU compiler library."""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention_paged import (decode_attention_paged,
                                                  decode_attention_paged_q8)
from repro.models.cache import kv_prefill_write, kv_token_write

# (q heads, kv heads, head_dim) of the served GQA configurations
WIDTHS = {
    "qwen3-1.7b": (16, 8, 128),
    "qwen2.5-7b": (28, 4, 128),       # the paper's model: group of 7
}
SLOTS, PAGES_PER_SLOT = 8, 16
# qwen3-1.7b's served K/V pool: [kv heads, 256 pages, 256 rows, head_dim]
POOL = (8, 256, 256, 128)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a rehearsal compile cannot be read back without a chip: keep it
    # out of any persistent cache the environment turned on
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _args(sharding, arch, block_size, quantized):
    h, kv, hd = WIDTHS[arch]
    n_blocks = 1 + SLOTS * PAGES_PER_SLOT

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    pages = (kv, n_blocks, block_size, hd)
    q = sds((SLOTS, h, hd), jnp.bfloat16)
    tables = sds((SLOTS, PAGES_PER_SLOT), jnp.int32)
    lengths = sds((SLOTS,), jnp.int32)
    if quantized:
        scales = sds(pages[:-1] + (1,), jnp.bfloat16)
        return (q, sds(pages, jnp.int8), scales, sds(pages, jnp.int8),
                scales, tables, lengths)
    return (q, sds(pages, jnp.bfloat16), sds(pages, jnp.bfloat16), tables,
            lengths)


@pytest.mark.parametrize("arch", sorted(WIDTHS))
@pytest.mark.parametrize("block_size", [16, 32])
@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "q8"])
def test_paged_decode_compiles_for_v5e(one_chip, arch, block_size,
                                       quantized):
    fn = decode_attention_paged_q8 if quantized else decode_attention_paged
    compiled = jax.jit(fn).lower(
        *_args(one_chip, arch, block_size, quantized)).compile()
    # each kernel carries its own name into the trace
    assert _kernel_names(compiled.as_text()) == {fn.__name__}


def _kernel_names(hlo):
    """Instruction names of the Mosaic custom calls, minus the ``.N``
    suffix XLA adds."""
    return {re.match(r"\s*%([\w-]+?)(\.\d+)? = ", line).group(1)
            for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


def test_windowed_paged_decode_compiles_for_v5e(one_chip):
    """The sliding-window ring mask (token-id masking) lowers too."""
    fn = jax.jit(decode_attention_paged, static_argnames=("window",))
    compiled = fn.lower(*_args(one_chip, "qwen3-1.7b", 16, False),
                        window=100).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("write,rows", [(kv_token_write, 8),
                                        (kv_prefill_write, 1024)],
                         ids=["token", "prefill"])
def test_kv_write_compiles_in_place_for_v5e(one_chip, write, rows):
    """A donated K/V write scatters into the pool's own buffer: the
    optimized program holds no copy or transpose as large as the pool
    (a write that keeps the head dim as a scatter window makes two, the
    pool into the scatter's layout and back)."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    pages = sds(POOL, jnp.bfloat16)
    vals = sds((rows,) + POOL[:1] + POOL[3:], jnp.bfloat16)
    if write is kv_token_write:
        rest = (sds((rows,), jnp.int32), sds((rows,), jnp.int32))
    else:                       # one slot's table, valid rows, span start
        rest = (sds((PAGES_PER_SLOT,), jnp.int32), sds((), jnp.int32),
                sds((), jnp.int32))
    hlo = jax.jit(write, donate_argnums=(0,)).lower(
        pages, vals, *rest).compile().as_text()
    pool_sized = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", hlo)
        if math.prod(int(x) for x in m.group(1).split(",")) ==
        math.prod(POOL)]
    assert pool_sized == []
