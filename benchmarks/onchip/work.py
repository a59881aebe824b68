"""Operations and least bytes of the served steps, and the chip's peaks.

Counted from the configuration's shapes and each step's live batch and
context lengths, which the harness records as the steps are dispatched.
Only useful work counts: rows padded into a batch bucket and positions
padded into a prefill bucket add time in the trace and nothing here, so
they show as a lower share.  A roofline time is the larger of operations
over peak FLOP/s and bytes over peak HBM bytes/s.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Sequence

from .model import Spec

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The chip's peaks by ``device_kind``; a chip not in the table is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def _head_bytes(spec: Spec) -> int:
    return 2 * spec.vocab * spec.d_model


def _weights_read(spec: Spec) -> int:
    """Weights a step reads once: every layer's matrices and the output
    head (the embedding rows it looks up are a few KB)."""
    return 2 * spec.layers * spec.layer_matmul_params() + _head_bytes(spec)


def decode_step(spec: Spec, ctx: Sequence[int]) -> tuple:
    """(FLOPs, bytes) of one decode step whose live rows have context
    lengths ``ctx`` (cached tokens including the one this step writes)."""
    a = len(ctx)
    per_tok = 2 * spec.layers * spec.layer_matmul_params() \
        + 2 * spec.d_model * spec.vocab
    attn = 4 * spec.layers * spec.heads * spec.head_dim * sum(ctx)
    kvb = spec.kv_bytes_per_token()
    return (a * per_tok + attn,
            _weights_read(spec) + kvb * sum(ctx) + kvb * a)


def prefill(spec: Spec, n: int) -> tuple:
    """(FLOPs, bytes) of a whole-prompt prefill of ``n`` tokens: causal
    attention, every layer, logits of the last position only."""
    per_tok = 2 * spec.layers * spec.layer_matmul_params()
    attn = 4 * spec.layers * spec.heads * spec.head_dim * n * (n + 1) // 2
    flops = n * per_tok + attn + 2 * spec.d_model * spec.vocab
    return flops, _weights_read(spec) + spec.kv_bytes_per_token() * n


def paged_attention(spec: Spec, ctx: Sequence[int]) -> tuple:
    """(FLOPs, bytes) of one ``decode_attention_paged`` call (one layer
    of one decode step): the live K/V once, q in and o out."""
    hd = spec.head_dim
    kv_tok = 2 * spec.kv_heads * hd * 2
    qo = 2 * len(ctx) * spec.heads * hd * 2
    return 4 * spec.heads * hd * sum(ctx), kv_tok * sum(ctx) + qo


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flop_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
