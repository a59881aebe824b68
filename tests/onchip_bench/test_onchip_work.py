"""The dense architecture module's work functions and weights tree, the
peaks table and the configuration files (CPU; counts and shapes only)."""
import dataclasses
import functools

import jax
import pytest

from conftest import ROOT
from onchip import weights, work
from onchip.model import load_arch, load_spec

CONFIGS = ROOT / "benchmarks" / "onchip" / "configs"
dense = load_arch("dense")

# a small spec whose counts are easy by hand: d=8, 2 layers, 2 q heads
# and 1 kv head of 4, MLP 16 (SwiGLU), vocab 10, tied
QWEN3 = load_spec(CONFIGS / "qwen3-1.7b.json")
SMALL = dataclasses.replace(
    QWEN3, name="small", layers=2,
    d_model=8, heads=2, kv_heads=1, head_dim=4, d_ff=16, vocab=10)
PEAK = {"bf16_flop_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_layer_and_head_counts_by_hand():
    # attention: q 8x8, k 8x4, v 8x4, o 8x8 = 192; MLP 3 x 8x16 = 384
    assert SMALL.layer_matmul_params() == 576
    assert SMALL.kv_bytes_per_token() == 2 * 2 * 1 * 4 * 2      # 32
    assert SMALL.weight_bytes() == 2 * (2 * 576 + 10 * 8)      # 2464


def test_decode_step_by_hand():
    flops, nbytes = dense.decode_step(SMALL, [3, 5])
    # per token 2*2*576 + 2*8*10 = 2464; attention 4*2 layers*2 heads*4*8
    assert flops == 2 * 2464 + 4 * 2 * 2 * 4 * 8
    # weights 2*2*576 + 2*10*8 = 2464; K/V read 8 tokens, written 2
    assert nbytes == 2464 + 32 * 8 + 32 * 2


def test_prefill_by_hand():
    flops, nbytes = dense.prefill(SMALL, 3)
    # 3 tokens x 2*2*576, causal attention 4*2*2*4*(3*4/2), head 2*8*10
    assert flops == 3 * 2304 + 4 * 2 * 2 * 4 * 6 + 160
    assert nbytes == 2464 + 32 * 3


def test_paged_attention_by_hand():
    flops, nbytes = dense.paged_attention(SMALL, [3, 5])
    assert flops == 4 * 2 * 4 * 8
    # K and V of one layer: 2 * 1 head * 4 * 2 bytes per token; q and o
    assert nbytes == 16 * 8 + 2 * 2 * 2 * 4 * 2


def test_roofline_takes_the_larger_bound():
    assert work.roofline_s(1000.0, 10.0, PEAK) == 10.0
    assert work.roofline_s(100.0, 100.0, PEAK) == 10.0


def test_v5e_peaks_and_unknown_device():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def test_configuration_sizes():
    assert QWEN3.kv_bytes_per_token() == 114_688
    assert QWEN3.weight_bytes() / 2e9 == pytest.approx(1.72, abs=0.01)


@pytest.mark.parametrize("spec", [
    QWEN3,
    # starcoder2-3b's block: LayerNorm, GELU MLP, GQA group 12, untied
    dataclasses.replace(QWEN3, layers=30, d_model=3072, heads=24,
                        kv_heads=2, d_ff=12288, vocab=49152,
                        norm="layernorm", mlp="gelu", qk_norm=False,
                        tied=False),
], ids=["qwen3-1.7b", "layernorm-gelu-untied"])
def test_weights_tree_is_the_programs_layout(spec):
    """The benchmark's tree has the leaves, shapes and dtypes of the
    program's own initialiser (shapes only, nothing is computed)."""
    from repro.models import init_params
    ours = jax.eval_shape(functools.partial(dense._tree, spec),
                          weights.seed_key(0))
    theirs = jax.eval_shape(functools.partial(
        init_params, cfg=dense.program_config(spec)), jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_seed_key_uses_every_bit():
    a = jax.random.key_data(weights.seed_key(5))
    b = jax.random.key_data(weights.seed_key(2**40 + 5))
    assert (a != b).any()
