"""Dispatching wrappers for the Pallas kernels on the model's path.

Every wrapper takes its kernel ``mode`` explicitly; there is no
process-wide switch.  An :class:`~repro.engine.engine.Engine` resolves
its mode once (:func:`resolve_mode`) and closes over it in its own jits,
so engines sharing a process never change each other's path.

  ``pallas``     the compiled kernel (TPU backends)
  ``interpret``  the same kernel under the Pallas interpreter (CPU tests)
  ``ref``        the pure-jnp oracle in ``ref.py`` (gather reference)
"""
from __future__ import annotations

from typing import Literal, Optional

import jax

from repro.kernels import ref as _ref
from repro.kernels.decode_attention_paged import (
    decode_attention_paged as _decode_paged_pallas,
    decode_attention_paged_q8 as _decode_paged_q8_pallas)
from repro.kernels.flash_attention import flash_attention as _flash_pallas

Mode = Literal["pallas", "interpret", "ref"]
MODES = ("pallas", "interpret", "ref")


def resolve_mode(mode: Optional[str] = None, tp: int = 1) -> str:
    """The paged-decode kernel mode an engine declares.

    ``None`` picks the compiled kernel on a TPU backend and the jnp
    reference elsewhere.  Under tensor parallelism (``tp > 1``) the paged
    kernel is not partitioned (``pallas_call`` does not auto-partition
    under GSPMD; its ``shard_map`` wrap is still open), so such an engine
    declares ``ref``; asking it for a kernel mode is an error.
    """
    if mode is not None and mode not in MODES:
        raise ValueError(f"kernel mode {mode!r} not in {MODES}")
    if tp > 1:
        if mode not in (None, "ref"):
            raise ValueError(
                f"kernel mode {mode!r} needs tp == 1; a tp={tp} engine "
                "decodes through the jnp gather reference ('ref')")
        return "ref"
    if mode is None:
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return mode


def flash_attention(q, k, v, *, mode: Mode, causal=True, window=0,
                    block_q=128, block_k=128):
    if mode == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         block_q=block_q, block_k=block_k,
                         interpret=(mode == "interpret"))


def decode_attention_paged(q, k_pages, v_pages, block_tables, lengths, *,
                           mode: Mode, window=0):
    """Flash-decode through a block table (kv-head-major paged pool)."""
    if mode == "ref":
        return _ref.decode_attention_paged_ref(q, k_pages, v_pages,
                                               block_tables, lengths,
                                               window=window)
    return _decode_paged_pallas(q, k_pages, v_pages, block_tables, lengths,
                                window=window,
                                interpret=(mode == "interpret"))


def decode_attention_paged_q8(q, k_pages, k_scale, v_pages, v_scale,
                              block_tables, lengths, *, mode: Mode,
                              window=0):
    """int8-KV paged flash-decode (per-(token, head) bf16 scales)."""
    if mode == "ref":
        from repro.models.cache import dequantize_kv
        kf = dequantize_kv(k_pages, k_scale)
        vf = dequantize_kv(v_pages, v_scale)
        return _ref.decode_attention_paged_ref(q, kf, vf, block_tables,
                                               lengths, window=window)
    return _decode_paged_q8_pallas(q, k_pages, k_scale, v_pages, v_scale,
                                   block_tables, lengths, window=window,
                                   interpret=(mode == "interpret"))
