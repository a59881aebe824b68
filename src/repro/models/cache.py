"""Decode caches: per-layer KV (full or ring-buffer window), MLA latent
cache, and SSM recurrent state.  A cache is a plain pytree:

{
  "pos":   [B] int32            # tokens generated so far (global position)
  "layers": [per-layer dict]    # kind-dependent
}

Layer kinds:
  attn  -> {"k": [B,L,kv,hd], "v": [B,L,kv,hd]}
  mla   -> {"ckv": [B,L,rank], "kpe": [B,L,rope_d]}
  ssm   -> {"conv": [B,K-1,conv_ch], "ssm": [B,nh,hd,ds] f32}

Two layouts share those kinds:

* **dense** (``init_cache``): one contiguous ``[B, L, ...]`` buffer per
  layer — HBM is priced by worst-case length per slot.
* **paged** (``init_paged_cache``): one shared block pool per layer
  (attention K/V kv-head-major ``[kv, num_blocks, block_size, hd]``,
  MLA latents ``[num_blocks, block_size, ...]``) plus per-slot block tables
  ``[B, pages_per_slot]`` — HBM is priced by *live tokens* (vLLM-style
  PagedAttention adapted to the static-shape TPU engine).  Block 0 is a
  reserved **null page**: unallocated table entries point at it and
  padded prefill tokens scatter into it, so every gather/scatter stays
  in-bounds without host-side masking.  SSM recurrent state stays
  per-slot (it is O(1) in sequence length).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ATTN, SSM, ModelConfig


def cache_len(cfg: ModelConfig, max_len: int) -> int:
    """Per-layer KV length: sliding-window archs only keep the window."""
    if cfg.sliding_window:
        return min(max_len, cfg.sliding_window)
    return max_len


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
               window: int = 0, quantized: bool = False):
    """window > 0 forces a ring-buffer of that size on attention layers
    (the StreamingLLM-style long-context serving mode).

    ``quantized``: int8 KV with per-(token, head) bf16 scales — halves the
    decode memory term (§Perf; vLLM-style kv-cache quantization adapted to
    the static-slot TPU layout)."""
    if dtype is None:
        dtype = jnp.dtype(cfg.dtype)
    L = cache_len(cfg, max_len)
    if window:
        L = min(L, window)
    layers = []
    for i, kind in enumerate(cfg.layer_pattern):
        if kind == SSM:
            s = cfg.ssm
            di = s.d_inner(cfg.d_model)
            layers.append({
                "conv": jnp.zeros((batch, s.d_conv - 1, di + 2 * s.d_state),
                                  dtype),
                "ssm": jnp.zeros((batch, s.n_heads(cfg.d_model), s.head_dim,
                                  s.d_state), jnp.float32),
            })
        elif cfg.mla is not None:
            m = cfg.mla
            layers.append({
                "ckv": jnp.zeros((batch, L, m.kv_lora_rank), dtype),
                "kpe": jnp.zeros((batch, L, m.qk_rope_head_dim), dtype),
            })
        else:
            if quantized:
                layers.append({
                    "k": jnp.zeros((batch, L, cfg.num_kv_heads,
                                    cfg.head_dim), jnp.int8),
                    "v": jnp.zeros((batch, L, cfg.num_kv_heads,
                                    cfg.head_dim), jnp.int8),
                    "k_scale": jnp.zeros((batch, L, cfg.num_kv_heads, 1),
                                         jnp.bfloat16),
                    "v_scale": jnp.zeros((batch, L, cfg.num_kv_heads, 1),
                                         jnp.bfloat16),
                })
            else:
                layers.append({
                    "k": jnp.zeros((batch, L, cfg.num_kv_heads,
                                    cfg.head_dim), dtype),
                    "v": jnp.zeros((batch, L, cfg.num_kv_heads,
                                    cfg.head_dim), dtype),
                })
    return {"pos": jnp.zeros((batch,), jnp.int32), "layers": layers}


def cache_spec(cfg: ModelConfig, batch: int, max_len: int, dtype=None,
               window: int = 0, quantized: bool = False):
    """ShapeDtypeStruct pytree mirroring ``init_cache`` (no allocation)."""
    return jax.eval_shape(
        lambda: init_cache(cfg, batch, max_len, dtype, window, quantized))


def quantize_kv(x):
    """x: [..., hd] -> (int8 values, bf16 scale [..., 1])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def dequantize_kv(q, scale):
    return q.astype(jnp.bfloat16) * scale


# ================================================================== paged
NULL_PAGE = 0           # reserved block: scatter/gather target for dead slots


def paged_slot_len(cfg: ModelConfig, max_len: int, block_size: int,
                   window: int = 0) -> int:
    """Logical per-slot ring length, rounded up to whole blocks."""
    L = cache_len(cfg, max_len)
    if window:
        L = min(L, window)
    return -(-L // block_size) * block_size


def init_paged_cache(cfg: ModelConfig, max_slots: int, max_len: int,
                     num_blocks: int, block_size: int = 16, dtype=None,
                     window: int = 0, quantized: bool = False):
    """Block-paged KV pool shared by ``max_slots`` request slots.

    {
      "pos":          [B] int32
      "block_tables": [B, pages_per_slot] int32   # 0 == null page
      "layers":       [per-layer dict]
    }

    attn -> {"k": [kv, num_blocks, block_size, hd], "v": ...}
            (+ "k_scale"/"v_scale" [kv, num_blocks, block_size, 1] when
            ``quantized``) — kv-head-major, so one page of one head is a
            contiguous ``[block_size, hd]`` tile (the paged kernel's block)
    mla  -> {"ckv": [num_blocks, block_size, rank], "kpe": [..., rope_d]}
    ssm  -> per-slot, identical to the dense layout.
    """
    if dtype is None:
        dtype = jnp.dtype(cfg.dtype)
    if num_blocks < 2:
        raise ValueError("num_blocks must be >= 2 (block 0 is the null page)")
    P = block_size
    layers = []
    for kind in cfg.layer_pattern:
        if kind == SSM:
            s = cfg.ssm
            di = s.d_inner(cfg.d_model)
            layers.append({
                "conv": jnp.zeros((max_slots, s.d_conv - 1,
                                   di + 2 * s.d_state), dtype),
                "ssm": jnp.zeros((max_slots, s.n_heads(cfg.d_model),
                                  s.head_dim, s.d_state), jnp.float32),
            })
        elif cfg.mla is not None:
            m = cfg.mla
            layers.append({
                "ckv": jnp.zeros((num_blocks, P, m.kv_lora_rank), dtype),
                "kpe": jnp.zeros((num_blocks, P, m.qk_rope_head_dim), dtype),
            })
        else:
            kv, hd = cfg.num_kv_heads, cfg.head_dim
            if quantized:
                layers.append({
                    "k": jnp.zeros((kv, num_blocks, P, hd), jnp.int8),
                    "v": jnp.zeros((kv, num_blocks, P, hd), jnp.int8),
                    "k_scale": jnp.zeros((kv, num_blocks, P, 1),
                                         jnp.bfloat16),
                    "v_scale": jnp.zeros((kv, num_blocks, P, 1),
                                         jnp.bfloat16),
                })
            else:
                layers.append({
                    "k": jnp.zeros((kv, num_blocks, P, hd), dtype),
                    "v": jnp.zeros((kv, num_blocks, P, hd), dtype),
                })
    pages_per_slot = paged_slot_len(cfg, max_len, P, window) // P
    return {"pos": jnp.zeros((max_slots,), jnp.int32),
            "block_tables": jnp.zeros((max_slots, pages_per_slot),
                                      jnp.int32),
            "layers": layers}


def paged_token_write(pages, vals, page_ids, offs):
    """Scatter one token per sequence into a latent pool.

    pages: [N, P, ...]; vals: [B, ...]; page_ids/offs: [B] int32.
    O(B) — independent of pool size (and in place under jit donation:
    the indexed dims are the major ones, so XLA scatters into the
    donated buffer with no layout copy)."""
    return pages.at[page_ids, offs].set(vals.astype(pages.dtype))


def kv_token_write(pages, vals, page_ids, offs):
    """Write rows into a kv-head-major K/V pool, in place: one new token
    per sequence, or a prefilled span (:func:`kv_prefill_write`).

    pages: [KV, N, P, d]; vals: [R, KV, d]; page_ids/offs: [R] int32.
    Each head's ``[N, P, d]`` pages take their rows as a latent pool does
    (:func:`paged_token_write`), vmapped with the indices batched too: one
    scatter with (head, page, offset) as its index dims, all major, and
    ``d`` as its window.  With the head dim left a window
    (``pages.at[:, page_ids, offs]``) TPU copies the whole pool into
    another layout and back around the scatter; folding the head into a
    flat row index instead would all-gather the rows of a head-sharded
    pool under a mesh.  O(R) under jit donation."""
    kv = pages.shape[0]
    page_ids = jnp.broadcast_to(page_ids, (kv,) + page_ids.shape)
    offs = jnp.broadcast_to(offs, (kv,) + offs.shape)
    return jax.vmap(paged_token_write)(pages, jnp.swapaxes(vals, 0, 1),
                                       page_ids, offs)


def _prefill_targets(block_table, S, P, n, start):
    """(page ids, in-page offsets) of a span of ``S`` rows at positions
    ``start .. start+S-1`` on a slot's logical ring of length
    ``pages_per_slot * P``.  Padding rows (beyond the first ``n``) and
    ring-evicted rows (when the span wraps) are routed to the null page,
    so duplicate in-bound indices never race."""
    L = block_table.shape[0] * P
    p = start + jnp.arange(S, dtype=jnp.int32)
    end = start + n
    keep = (p < end) & (p >= end - L)
    widx = jnp.mod(p, L)
    return (jnp.where(keep, block_table[widx // P], NULL_PAGE),
            jnp.mod(widx, P))


def paged_prefill_write(pages, vals, block_table, n, start=0):
    """Scatter a prefilled span of ONE slot into its latent pages.

    pages: [N, P, ...]; vals: [S, ...] (first ``n`` rows valid — the rest
    are padding); block_table: [pages_per_slot] int32.  O(S) — no
    O(pool) commit copy."""
    page_ids, offs = _prefill_targets(block_table, vals.shape[0],
                                      pages.shape[1], n, start)
    return pages.at[page_ids, offs].set(vals.astype(pages.dtype))


def kv_prefill_write(pages, vals, block_table, n, start=0):
    """:func:`paged_prefill_write` for a kv-head-major K/V pool.

    pages: [KV, N, P, d]; vals: [S, KV, d]."""
    page_ids, offs = _prefill_targets(block_table, vals.shape[0],
                                      pages.shape[2], n, start)
    return kv_token_write(pages, vals, page_ids, offs)


def write_prefill_paged(cache, layer_idx: int, kv_tuple, cfg: ModelConfig,
                        slot, n):
    """Paged counterpart of :func:`write_prefill`: write one request's
    full-sequence K/V (or latent / SSM state) produced by a prefill pass
    into ``slot``'s pages at positions [0, n).  ``kv_tuple`` entries are
    [1, S, ...] (S >= n; tail is padding)."""
    layer = cache["layers"][layer_idx]
    bt = cache["block_tables"][slot]
    if "ssm" in layer:
        conv, ssm = kv_tuple
        layer = {"conv": layer["conv"].at[slot].set(
                     conv[0].astype(layer["conv"].dtype)),
                 "ssm": layer["ssm"].at[slot].set(ssm[0])}
    elif "ckv" in layer:
        ckv, kpe = kv_tuple
        layer = {
            "ckv": paged_prefill_write(layer["ckv"], ckv[0], bt, n),
            "kpe": paged_prefill_write(layer["kpe"], kpe[0], bt, n),
        }
    else:
        k, v = kv_tuple
        if "k_scale" in layer:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            layer = {
                "k": kv_prefill_write(layer["k"], kq[0], bt, n),
                "v": kv_prefill_write(layer["v"], vq[0], bt, n),
                "k_scale": kv_prefill_write(layer["k_scale"], ks[0], bt, n),
                "v_scale": kv_prefill_write(layer["v_scale"], vs[0], bt, n),
            }
        else:
            layer = {
                "k": kv_prefill_write(layer["k"], k[0], bt, n),
                "v": kv_prefill_write(layer["v"], v[0], bt, n),
            }
    cache["layers"][layer_idx] = layer
    return cache


def copy_page(cache, src: int, dst: int):
    """Copy one KV page across every paged layer: the device half of
    copy-on-write.  A writer about to touch a block other owners still
    share gets a private copy at ``dst`` first (host side: fresh alloc +
    block-table patch in the engine).  Per-slot state (SSM) is untouched
    — it is never shared."""
    for i, layer in enumerate(cache["layers"]):
        if "conv" in layer:
            continue
        if "ckv" in layer:
            cache["layers"][i] = {k: v.at[dst].set(v[src])
                                  for k, v in layer.items()}
        else:                       # kv-head-major: block axis is 1
            cache["layers"][i] = {k: v.at[:, dst].set(v[:, src])
                                  for k, v in layer.items()}
    return cache


def gather_pages(pages, block_tables):
    """Materialize the logical [B, L, ...] view of a latent paged layer.

    pages: [N, P, ...]; block_tables: [B, pages_per_slot].  Gathers live
    pages only — the MLA decode read."""
    b, npg = block_tables.shape
    g = pages[block_tables]                     # [B, pages, P, ...]
    return g.reshape((b, npg * pages.shape[1]) + pages.shape[2:])


def gather_kv_pages(pages, block_tables):
    """Materialize the logical [B, L, KV, d] view of a kv-head-major K/V
    pool [KV, N, P, d] — the jnp gather reference for the Pallas
    paged-decode kernel and the chunked-prefill prefix read."""
    kv, _, P, d = pages.shape
    b, npg = block_tables.shape
    g = jnp.moveaxis(pages[:, block_tables], 0, 3)   # [B, pages, P, KV, d]
    return g.reshape(b, npg * P, kv, d)


def kv_bytes_per_token(cfg: ModelConfig, dtype=None,
                       quantized: bool = False) -> int:
    """HBM bytes of KV (or MLA latent) cache per token, across layers.
    SSM layers contribute 0 (their state is O(1) in sequence length)."""
    if dtype is None:
        dtype = jnp.dtype(cfg.dtype)
    size = jnp.dtype(dtype).itemsize
    total = 0
    for kind in cfg.layer_pattern:
        if kind == SSM:
            continue
        if cfg.mla is not None:
            total += (cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim) * size
        elif quantized:
            # int8 values + one bf16 scale per (token, head) for k and v
            total += 2 * cfg.num_kv_heads * (cfg.head_dim * 1 + 2)
        else:
            total += 2 * cfg.num_kv_heads * cfg.head_dim * size
    return total


def _ring_write(buf, vals):
    """Write a prefilled sequence into a ring buffer of length L, keeping the
    ring invariant ``token t lives at slot t % L``.

    buf: [B, L, ...]; vals: [B, S, ...] (tokens 0..S-1).
    """
    L = buf.shape[1]
    s = vals.shape[1]
    vals = vals.astype(buf.dtype)
    if s < L:
        return jax.lax.dynamic_update_slice(
            buf, vals, (0,) * buf.ndim)
    kept = vals[:, s - L:]              # tokens s-L .. s-1, in order
    return jnp.roll(kept, shift=s % L, axis=1)


def write_prefill(cache, layer_idx: int, kv_tuple, cfg: ModelConfig):
    """Write full-sequence K/V (or latent) produced by a prefill pass into
    the cache at positions [0, S)."""
    layer = cache["layers"][layer_idx]
    if "ssm" in layer:
        conv, ssm = kv_tuple
        layer = {"conv": conv.astype(layer["conv"].dtype), "ssm": ssm}
    elif "ckv" in layer:
        ckv, kpe = kv_tuple
        layer = {
            "ckv": _ring_write(layer["ckv"], ckv),
            "kpe": _ring_write(layer["kpe"], kpe),
        }
    else:
        k, v = kv_tuple
        if "k_scale" in layer:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            layer = {
                "k": _ring_write(layer["k"], kq),
                "v": _ring_write(layer["v"], vq),
                "k_scale": _ring_write(layer["k_scale"], ks),
                "v_scale": _ring_write(layer["v_scale"], vs),
            }
        else:
            layer = {
                "k": _ring_write(layer["k"], k),
                "v": _ring_write(layer["v"], v),
            }
    cache["layers"][layer_idx] = layer
    return cache
