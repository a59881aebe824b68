"""The dense architecture module gives, operation for operation, what the
benchmark gave before configurations named their architecture: the same
weights tree, the same reference gaps and the same least-work counts.
Every value here was computed on the CPU with the benchmark's shared
modules before the dense decoder moved into ``archs/dense.py``."""
import hashlib

import jax
import numpy as np
import pytest

from conftest import ROOT, TINY_CONFIG
from onchip.model import load_arch, load_spec

dense = load_arch("dense")
QWEN3 = load_spec(ROOT / "benchmarks" / "onchip" / "configs"
                  / "qwen3-1.7b.json")
# a fixed prompt and served sequence (not the model's greedy tokens, so
# every gap is well away from zero)
PROMPT = np.array([(7 * i + 3) % 256 for i in range(21)], np.int32)
SERVED = [(11 * i + 5) % 256 for i in range(6)]


def _tree_hash(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}|{a.dtype}|{a.shape}|"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_weights_are_bit_identical():
    params = dense.make_params(dense.load_spec(TINY_CONFIG), 7)
    assert _tree_hash(params) == (
        "198db35f191979fb9e4d47e1cf37b3ac6e23afba65c5640ebb6e7fd6aec5e513")


def test_reference_and_control_gaps_are_identical():
    spec = dense.load_spec(TINY_CONFIG)
    ref = dense.Reference(spec, dense.make_params(spec, 7), control=True)
    g, c = ref.gaps(PROMPT, SERVED)
    assert [float(x) for x in g] == [
        4.147332191467285, 2.9413928985595703, 4.82729959487915,
        2.3378713130950928, 4.812591552734375, 4.357989311218262]
    assert [float(x) for x in c] == [0.21054744720458984, 0, 0, 0, 0, 0]


CTX = {"one": [1], "two": [100, 300], "full": [4096] * 64,
       "ramp": list(range(1, 65))}


@pytest.mark.parametrize("ctx,want", [
    ("one", (3441131520, 3441131520)),
    ("two", (6973554688, 3487006720)),
    ("full", (280347279360, 33513013248)),
    ("ramp", (220694839296, 3686793216)),
])
def test_decode_step_counts_are_identical(ctx, want):
    assert dense.decode_step(QWEN3, CTX[ctx]) == want


@pytest.mark.parametrize("n,want", [
    (1, (3441131520, 3441016832)),
    (37, (105070755840, 3445145600)),
    (512, (1473854832640, 3499622400)),
    (4095, (13466351435776, 3910549504)),
])
def test_prefill_counts_are_identical(n, want):
    assert dense.prefill(QWEN3, n) == want


@pytest.mark.parametrize("ctx,want", [
    ("one", (8192, 12288)),
    ("two", (3276800, 1654784)),
    ("full", (2147483648, 1074266112)),
    ("ramp", (17039360, 9043968)),
])
def test_paged_attention_counts_are_identical(ctx, want):
    assert dense.paged_attention(QWEN3, CTX[ctx]) == want
