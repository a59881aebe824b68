"""Attention blocks: GQA (RoPE / M-RoPE, qk-norm, sliding window) and MLA.

Two execution paths per block:
  * ``full``  — prefill / train over a whole sequence with a causal
    (optionally sliding-window) mask; optionally writes a KV cache.
  * ``decode`` — a single new token attending to a cache.

MLA (DeepSeek-V2) uses the compressed-KV cache with the *absorbed* decode
formulation: scores are computed directly against the latent cache, so the
per-token decode cost is O(L · (kv_lora + rope_dim)) instead of
O(L · heads · head_dim).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, rmsnorm, rope_cos_sin

NEG_INF = -1e30


def _constrain(x, ctx, *spec):
    """with_sharding_constraint when a mesh ctx is present (no-op otherwise)."""
    if ctx is None or getattr(ctx, "mesh", None) is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*spec)))


def _use_attn_kernel(cfg, s: int, attn_kernel) -> bool:
    if attn_kernel is None or cfg.attn_logit_softcap:
        return False
    return s >= 16 and s % 16 == 0


def _seq_parallel_wanted(cfg, ctx, s: int) -> bool:
    if ctx is None or getattr(ctx, "mesh", None) is None:
        return False
    if getattr(ctx, "attn_sharding", "none") != "auto" or s <= 1:
        return False
    return s % ctx.mesh.shape[ctx.tp_axis] == 0


# =================================================================== GQA
def init_gqa(key, cfg, dtype):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(ks[0], (d, h * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, kv * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, kv * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h * hd, d)) * (h * hd) ** -0.5).astype(dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _expand_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B,S,kv,hd] -> [B,S,kv*n_rep,hd]."""
    if n_rep == 1:
        return k
    b, s, kv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, hd)
                            ).reshape(b, s, kv * n_rep, hd)


def _causal_mask(q_len: int, kv_len: int, q_offset, window: int) -> jax.Array:
    """[q_len, kv_len] bool; True = attend. q position i sits at q_offset+i."""
    qpos = q_offset + jnp.arange(q_len)[:, None]
    kpos = jnp.arange(kv_len)[None, :]
    m = kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m


def gqa_full(params, cfg, x, positions, *, cache_len: int = 0, ctx=None,
             attn_kernel=None):
    """Full-sequence attention.

    x: [B, S, D]; positions: [B, S] (or [B, S, 3] for M-RoPE).
    Returns (out [B,S,D], (k, v) [B,S,kv,hd] for cache writing).
    ``attn_kernel`` (a ``kernels.ops`` mode, default off) routes the
    attention through the flash-attention dispatcher.

    With ``ctx.attn_sharding == "auto"``, sequence-parallel constraints are
    applied: q (and the scores' q dim) shard over the tp axis while k/v are
    replicated within the tp group — correct for ANY head count, unlike
    head sharding which needs h % tp == 0 (§Perf: fixes the giant score
    all-reduces GSPMD emits for h=24/28 archs).
    """
    b, s, d = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("attn_proj"):
        q = jnp.dot(x, params["wq"]).reshape(b, s, h, hd)
        k = jnp.dot(x, params["wk"]).reshape(b, s, kv, hd)
        v = jnp.dot(x, params["wv"]).reshape(b, s, kv, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
        cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta,
                                cfg.mrope_sections)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    seq_par = _seq_parallel_wanted(cfg, ctx, s)
    if seq_par:
        tp = ctx.tp_axis
        dpb = ctx.dp_axes if b % _axes_prod(ctx) == 0 else None
        q = _constrain(q, ctx, dpb, tp, None, None)
        k = _constrain(k, ctx, dpb, None, None, None)
        v = _constrain(v, ctx, dpb, None, None, None)
    if not seq_par and _use_attn_kernel(cfg, s, attn_kernel):
        from repro.kernels import ops
        with jax.named_scope("attend"):
            out = ops.flash_attention(q, k, v, mode=attn_kernel,
                                      causal=True, window=cfg.sliding_window)
            out = out.reshape(b, s, h * hd)
        with jax.named_scope("attn_proj"):
            return jnp.dot(out, params["wo"]), (k, v)
    with jax.named_scope("attend"):
        rep = h // kv
        qg = q.reshape(b, s, kv, rep, hd)
        mask = _causal_mask(s, s, 0, cfg.sliding_window)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k) \
            / jnp.sqrt(hd).astype(x.dtype)
        if seq_par:
            scores = _constrain(scores, ctx, dpb, None, None, ctx.tp_axis,
                                None)
        if cfg.attn_logit_softcap:
            c = cfg.attn_logit_softcap
            scores = c * jnp.tanh(scores / c)
        scores = jnp.where(mask[None, None, None],
                           scores.astype(jnp.float32), NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs,
                         v).reshape(b, s, h * hd)
        if seq_par:
            out = _constrain(out, ctx, dpb, ctx.tp_axis, None)
    with jax.named_scope("attn_proj"):
        return jnp.dot(out, params["wo"]), (k, v)


def _axes_prod(ctx) -> int:
    n = 1
    for a in ctx.dp_axes:
        n *= ctx.mesh.shape[a]
    return n


def _ring_token_write(cache, val, widx):
    return jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u, (i,) + (0,) * (c.ndim - 1)))(cache, val.astype(cache.dtype),
                                           widx)


def gqa_decode(params, cfg, x, positions, k_cache, v_cache, cache_pos,
               k_scale=None, v_scale=None):
    """One-token decode against a cache.

    x: [B, 1, D]; k_cache/v_cache: [B, L, kv, hd]; cache_pos: [B] int32 —
    number of valid tokens already in the cache.  Returns
    (out [B,1,D], new cache entries dict).
    For sliding-window configs the cache is a ring buffer of length
    min(L, window) and positions wrap.  With int8 caches (k_scale given)
    the new token is quantized per (token, head) and the cache is
    dequantized inside the score/value contractions (fused on TPU).
    """
    from repro.models.cache import dequantize_kv, quantize_kv
    b, s, d = x.shape
    assert s == 1
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L = k_cache.shape[1]
    q = jnp.dot(x, params["wq"]).reshape(b, 1, h, hd)
    k = jnp.dot(x, params["wk"]).reshape(b, 1, kv, hd)
    v = jnp.dot(x, params["wv"]).reshape(b, 1, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta, cfg.mrope_sections)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # ring-buffer write index
    widx = jnp.mod(cache_pos, L)  # [B]
    quant = k_scale is not None
    if quant:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        k_cache = _ring_token_write(k_cache, kq, widx)
        v_cache = _ring_token_write(v_cache, vq, widx)
        k_scale = _ring_token_write(k_scale, ks, widx)
        v_scale = _ring_token_write(v_scale, vs, widx)
        k_eff = dequantize_kv(k_cache, k_scale)
        v_eff = dequantize_kv(v_cache, v_scale)
    else:
        k_cache = _ring_token_write(k_cache, k, widx)
        v_cache = _ring_token_write(v_cache, v, widx)
        k_eff, v_eff = k_cache, v_cache
    n_valid = jnp.minimum(cache_pos + 1, L)  # [B]
    # grouped-GQA form: never materialize the head-expanded cache — the
    # cache keeps its (possibly sequence-sharded) layout and the partitioner
    # reduces over the sharded L dim with small collectives.
    rep = h // kv
    qg = q.reshape(b, kv, rep, hd)  # [B,kv,rep,hd]
    scores = jnp.einsum("bgrd,blgd->bgrl", qg,
                        k_eff.astype(qg.dtype)) \
        / jnp.sqrt(hd).astype(x.dtype)
    if cfg.attn_logit_softcap:
        c = cfg.attn_logit_softcap
        scores = c * jnp.tanh(scores / c)
    valid = jnp.arange(L)[None, :] < n_valid[:, None]  # [B, L]
    scores = jnp.where(valid[:, None, None, :], scores.astype(jnp.float32),
                       NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bgrl,blgd->bgrd", probs,
                     v_eff.astype(probs.dtype)).reshape(b, 1, h * hd)
    new_cache = {"k": k_cache, "v": v_cache}
    if quant:
        new_cache.update(k_scale=k_scale, v_scale=v_scale)
    return jnp.dot(out, params["wo"]), new_cache


# =================================================================== MLA
def init_mla(key, cfg, dtype):
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    r = m.kv_lora_rank ** -0.5
    return {
        "wq": (jax.random.normal(ks[0], (d, h * qk_hd)) * s).astype(dtype),
        "w_dkv": (jax.random.normal(ks[1], (d, m.kv_lora_rank)) * s).astype(dtype),
        "w_krope": (jax.random.normal(ks[2], (d, m.qk_rope_head_dim)) * s).astype(dtype),
        "w_uk": (jax.random.normal(ks[3], (m.kv_lora_rank, h * m.qk_nope_head_dim)) * r).astype(dtype),
        "w_uv": (jax.random.normal(ks[4], (m.kv_lora_rank, h * m.v_head_dim)) * r).astype(dtype),
        "wo": (jax.random.normal(ks[5], (h * m.v_head_dim, d))
               * (h * m.v_head_dim) ** -0.5).astype(dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), jnp.float32),
    }


def mla_full(params, cfg, x, positions, **_):
    """MLA prefill/train: expand the latent and run standard attention.

    Returns (out, (c_kv, k_rope)) for cache writing.
    """
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.num_heads
    nope, rope_d, vhd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    q = jnp.dot(x, params["wq"]).reshape(b, s, h, nope + rope_d)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    c_kv = rmsnorm(jnp.dot(x, params["w_dkv"]), params["kv_norm"], cfg.norm_eps)
    k_pe = jnp.dot(x, params["w_krope"]).reshape(b, s, 1, rope_d)
    cos, sin = rope_cos_sin(positions, rope_d, cfg.rope_theta)
    q_pe = apply_rope(q_pe, cos, sin)
    k_pe = apply_rope(k_pe, cos, sin)
    k_nope = jnp.dot(c_kv, params["w_uk"]).reshape(b, s, h, nope)
    v = jnp.dot(c_kv, params["w_uv"]).reshape(b, s, h, vhd)
    scale = 1.0 / jnp.sqrt(nope + rope_d).astype(x.dtype)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkhd->bhqk", q_pe,
                           jnp.broadcast_to(k_pe, (b, s, h, rope_d)))) * scale
    mask = _causal_mask(s, s, 0, 0)
    scores = jnp.where(mask[None, None], scores.astype(jnp.float32), NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * vhd)
    return jnp.dot(out, params["wo"]), (c_kv, k_pe[:, :, 0, :])


def mla_decode(params, cfg, x, positions, ckv_cache, kpe_cache, cache_pos):
    """Absorbed MLA decode: attend in the latent space.

    ckv_cache: [B, L, kv_lora]; kpe_cache: [B, L, rope_d].
    score_l = q_nope_h · W_uk_h · c_l + q_pe_h · kpe_l
    out_h   = (Σ p_l c_l) · W_uv_h
    """
    m = cfg.mla
    b, s, d = x.shape
    assert s == 1
    h = cfg.num_heads
    nope, rope_d, vhd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    rank = m.kv_lora_rank
    L = ckv_cache.shape[1]
    q = jnp.dot(x, params["wq"]).reshape(b, 1, h, nope + rope_d)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    c_kv = rmsnorm(jnp.dot(x, params["w_dkv"]), params["kv_norm"], cfg.norm_eps)  # [B,1,rank]
    k_pe = jnp.dot(x, params["w_krope"]).reshape(b, 1, 1, rope_d)
    cos, sin = rope_cos_sin(positions, rope_d, cfg.rope_theta)
    q_pe = apply_rope(q_pe, cos, sin)
    k_pe = apply_rope(k_pe, cos, sin)[:, :, 0, :]  # [B,1,rope_d]
    widx = jnp.mod(cache_pos, L)
    ckv_cache = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u, (i, 0)))(ckv_cache, c_kv.astype(ckv_cache.dtype), widx)
    kpe_cache = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(
        c, u, (i, 0)))(kpe_cache, k_pe.astype(kpe_cache.dtype), widx)
    n_valid = jnp.minimum(cache_pos + 1, L)
    # absorb W_uk into q:  q_abs [B,h,rank]
    w_uk = params["w_uk"].reshape(rank, h, nope)
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
    scale = 1.0 / jnp.sqrt(nope + rope_d)
    scores = (jnp.einsum("bhr,blr->bhl", q_abs.astype(jnp.float32),
                         ckv_cache.astype(jnp.float32))
              + jnp.einsum("bhd,bld->bhl", q_pe[:, 0].astype(jnp.float32),
                           kpe_cache.astype(jnp.float32))) * scale
    valid = jnp.arange(L)[None, :] < n_valid[:, None]
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhl,blr->bhr", probs,
                     ckv_cache.astype(jnp.float32)).astype(x.dtype)  # [B,h,rank]
    w_uv = params["w_uv"].reshape(rank, h, vhd)
    out = jnp.einsum("bhr,rhd->bhd", ctx, w_uv).reshape(b, 1, h * vhd)
    return jnp.dot(out, params["wo"]), ckv_cache, kpe_cache


def _chunk_attend(params, cfg, x, positions, k_prefix, v_prefix, start_pos):
    """Shared chunk-continuation attention over a materialized prefix.

    A chunk of C tokens at absolute positions [start_pos, start_pos+C)
    attends to the cached prefix (ring, token-id masked) plus itself
    (intra-chunk causal).  The caller writes the chunk's K/V into its
    cache layout (dense ring or paged pool) afterwards, so in-chunk
    evictions cannot clobber keys still needed by earlier queries.

    x: [B, C, D]; k_prefix/v_prefix: [B, L, kv, hd] (the logical cache
    view); start_pos: int/traced.  Returns (out [B,C,D], k_chunk,
    v_chunk [B,C,kv,hd]).
    """
    b, c, d = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L = k_prefix.shape[1]
    assert c <= L, "chunk larger than the cache ring"
    q = jnp.dot(x, params["wq"]).reshape(b, c, h, hd)
    k = jnp.dot(x, params["wk"]).reshape(b, c, kv, hd)
    v = jnp.dot(x, params["wv"]).reshape(b, c, kv, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta, cfg.mrope_sections)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    sp = jnp.asarray(start_pos, jnp.int32)
    window = cfg.sliding_window
    rep = h // kv
    qg = q.reshape(b, c, kv, rep, hd)
    scale = 1.0 / jnp.sqrt(hd).astype(x.dtype)
    qpos = sp + jnp.arange(c, dtype=jnp.int32)[:, None]        # [c,1]

    # ---- part 1: cached prefix (tokens < sp), ring token-id masking
    slots = jnp.arange(L, dtype=jnp.int32)[None, :]            # [1,L]
    # largest token id t == slot (mod L) with t < sp
    t_slot = sp - 1 - jnp.mod(sp - 1 - slots, L)               # [1,L]
    m_pre = (t_slot >= 0) & (t_slot <= qpos)
    if window > 0:
        m_pre &= t_slot > qpos - window
    s_pre = jnp.einsum("bqgrd,blgd->bgrql", qg,
                       k_prefix.astype(qg.dtype)) * scale
    s_pre = jnp.where(m_pre[None, None, None], s_pre.astype(jnp.float32),
                      NEG_INF)

    # ---- part 2: the fresh chunk, intra-chunk causal
    cpos = sp + jnp.arange(c, dtype=jnp.int32)[None, :]        # [1,c]
    m_chk = cpos <= qpos
    if window > 0:
        m_chk &= cpos > qpos - window
    s_chk = jnp.einsum("bqgrd,bcgd->bgrqc", qg, k) * scale
    s_chk = jnp.where(m_chk[None, None, None], s_chk.astype(jnp.float32),
                      NEG_INF)

    scores = jnp.concatenate([s_pre, s_chk], axis=-1)          # [b,g,r,q,L+c]
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bgrql,blgd->bqgrd", probs[..., :L],
                     v_prefix.astype(probs.dtype)) + \
        jnp.einsum("bgrqc,bcgd->bqgrd", probs[..., L:], v)
    out = out.reshape(b, c, h * hd)
    return jnp.dot(out, params["wo"]), k, v


def gqa_continue(params, cfg, x, positions, k_cache, v_cache, start_pos):
    """Chunked-prefill continuation (Sarathi-style) on the dense layout.

    Ring-safe: the cache may be a window ring (slot t%L holds token t).
    x: [B, C, D]; k_cache/v_cache: [B, L, kv, hd]; start_pos: int/traced.
    Returns (out [B,C,D], new_k_cache, new_v_cache).
    """
    L = k_cache.shape[1]
    c = x.shape[1]
    out, k, v = _chunk_attend(params, cfg, x, positions, k_cache, v_cache,
                              start_pos)
    # ---- deferred ring write of the chunk
    sp = jnp.asarray(start_pos, jnp.int32)
    widx = jnp.mod(sp + jnp.arange(c, dtype=jnp.int32), L)
    k_cache = k_cache.at[:, widx].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[:, widx].set(v.astype(v_cache.dtype))
    return out, k_cache, v_cache


# ============================================================== paged GQA
def _paged_write_token(layer_cache, k, v, block_tables, cache_pos,
                       quantized: bool):
    """Write one new token per sequence into its page (O(B) scatter).

    k/v: [B, 1, kv, hd]; returns the updated layer dict."""
    from repro.models.cache import kv_token_write, quantize_kv
    P = layer_cache["k"].shape[2]
    L = block_tables.shape[1] * P
    widx = jnp.mod(cache_pos, L)                              # [B]
    page_ids = jnp.take_along_axis(block_tables, (widx // P)[:, None],
                                   axis=1)[:, 0]
    offs = jnp.mod(widx, P)
    if quantized:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        vals = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        vals = {"k": k, "v": v}
    return {name: kv_token_write(layer_cache[name], x[:, 0], page_ids, offs)
            for name, x in vals.items()}


def gqa_decode_paged(params, cfg, x, positions, layer_cache, block_tables,
                     cache_pos, kernel_mode: str = "ref"):
    """One-token decode against the paged KV pool.

    x: [B, 1, D]; layer_cache: {"k","v"[,"k_scale","v_scale"]} page
    arrays [kv, N, P, hd]; block_tables: [B, pages_per_slot] int32;
    cache_pos: [B] int32.  The new token is written in place into its
    page (O(B), not O(pool)), then attention runs through the block
    table in ``kernel_mode`` (``kernels.ops``: the compiled Pallas paged
    flash-decode kernel, its interpreter, or the jnp gather reference).
    Returns (out [B,1,D], new layer dict).
    """
    from repro.kernels import ops
    from repro.models.cache import dequantize_kv, gather_kv_pages
    b, s, d = x.shape
    assert s == 1
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("attn_proj"):
        q = jnp.dot(x, params["wq"]).reshape(b, 1, h, hd)
        k = jnp.dot(x, params["wk"]).reshape(b, 1, kv, hd)
        v = jnp.dot(x, params["wv"]).reshape(b, 1, kv, hd)
        if cfg.qk_norm:
            q = rmsnorm(q, params["q_norm"], cfg.norm_eps)
            k = rmsnorm(k, params["k_norm"], cfg.norm_eps)
        cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta,
                                cfg.mrope_sections)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    quant = "k_scale" in layer_cache
    with jax.named_scope("kv_write"):
        new_cache = _paged_write_token(layer_cache, k, v, block_tables,
                                       cache_pos, quant)
    lengths = cache_pos + 1
    with jax.named_scope("attend"):
        if cfg.attn_logit_softcap:
            # the paged kernel (like the dense one) has no logit softcap —
            # gather the live pages and run the einsum path
            P = new_cache["k"].shape[2]
            L = block_tables.shape[1] * P
            k_eff = gather_kv_pages(new_cache["k"], block_tables)
            v_eff = gather_kv_pages(new_cache["v"], block_tables)
            if quant:
                k_eff = dequantize_kv(k_eff, gather_kv_pages(
                    new_cache["k_scale"], block_tables))
                v_eff = dequantize_kv(v_eff, gather_kv_pages(
                    new_cache["v_scale"], block_tables))
            rep = h // kv
            qg = q.reshape(b, kv, rep, hd)
            scores = jnp.einsum("bgrd,blgd->bgrl", qg,
                                k_eff.astype(qg.dtype)) \
                / jnp.sqrt(hd).astype(x.dtype)
            c = cfg.attn_logit_softcap
            scores = c * jnp.tanh(scores / c)
            ln = lengths[:, None]
            s_idx = jnp.arange(L, dtype=jnp.int32)[None, :]
            t_s = ln - 1 - jnp.mod(ln - 1 - s_idx, L)
            valid = t_s >= 0
            if cfg.sliding_window > 0:
                valid &= t_s > ln - 1 - cfg.sliding_window
            scores = jnp.where(valid[:, None, None, :],
                               scores.astype(jnp.float32), NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            o = jnp.einsum("bgrl,blgd->bgrd", probs,
                           v_eff.astype(probs.dtype)).reshape(b, h, hd)
        elif quant:
            o = ops.decode_attention_paged_q8(
                q[:, 0], new_cache["k"], new_cache["k_scale"], new_cache["v"],
                new_cache["v_scale"], block_tables, lengths, mode=kernel_mode,
                window=cfg.sliding_window)
        else:
            o = ops.decode_attention_paged(
                q[:, 0], new_cache["k"], new_cache["v"], block_tables, lengths,
                mode=kernel_mode, window=cfg.sliding_window)
    with jax.named_scope("attn_proj"):
        out = o.astype(x.dtype).reshape(b, 1, h * hd)
        return jnp.dot(out, params["wo"]), new_cache


def gqa_continue_paged(params, cfg, x, positions, layer_cache, block_tables,
                       start_pos, n=None):
    """Chunked-prefill continuation on the paged pool (single slot).

    x: [B, C, D] (B = 1 slot); the prefix is gathered through the block
    table (dequantized for int8 caches), the chunk is scattered into its
    pages afterwards (O(C); quantized with fresh per-token scales).
    ``n`` (static or traced; default C) is the number of *valid* chunk
    rows — padded suffix-prefill buckets write only their valid span,
    while padded keys beyond it stay causally masked out of every valid
    query anyway.  Returns (out [B,C,D], new layer dict).
    """
    from repro.models.cache import (dequantize_kv, gather_kv_pages,
                                    kv_prefill_write, quantize_kv)
    c = x.shape[1]
    if n is None:
        n = c
    quant = "k_scale" in layer_cache
    k_prefix = gather_kv_pages(layer_cache["k"], block_tables)
    v_prefix = gather_kv_pages(layer_cache["v"], block_tables)
    if quant:
        k_prefix = dequantize_kv(k_prefix, gather_kv_pages(
            layer_cache["k_scale"], block_tables))
        v_prefix = dequantize_kv(v_prefix, gather_kv_pages(
            layer_cache["v_scale"], block_tables))
    out, k, v = _chunk_attend(params, cfg, x, positions, k_prefix, v_prefix,
                              start_pos)
    bt = block_tables[0]

    def write(pages, vals):
        return kv_prefill_write(pages, vals[0], bt, n, start=start_pos)
    if quant:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return out, {"k": write(layer_cache["k"], kq),
                     "v": write(layer_cache["v"], vq),
                     "k_scale": write(layer_cache["k_scale"], ks),
                     "v_scale": write(layer_cache["v_scale"], vs)}
    return out, {"k": write(layer_cache["k"], k),
                 "v": write(layer_cache["v"], v)}


def mla_decode_paged(params, cfg, x, positions, ckv_pages, kpe_pages,
                     block_tables, cache_pos):
    """Absorbed MLA decode against paged latent caches.

    The new latent token is written in place into its page, then the
    live pages are gathered into the logical [B, L, rank] view and the
    dense absorbed-decode math runs on it (the latent is too narrow for
    a per-kv-head kernel tile; capacity, not decode reads, is what
    paging buys MLA archs).  Returns (out, new_ckv_pages, new_kpe_pages).
    """
    from repro.models.cache import gather_pages, paged_token_write
    m = cfg.mla
    b, s, d = x.shape
    assert s == 1
    h = cfg.num_heads
    nope, rope_d, vhd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    rank = m.kv_lora_rank
    P = ckv_pages.shape[1]
    L = block_tables.shape[1] * P
    q = jnp.dot(x, params["wq"]).reshape(b, 1, h, nope + rope_d)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    c_kv = rmsnorm(jnp.dot(x, params["w_dkv"]), params["kv_norm"],
                   cfg.norm_eps)                              # [B,1,rank]
    k_pe = jnp.dot(x, params["w_krope"]).reshape(b, 1, 1, rope_d)
    cos, sin = rope_cos_sin(positions, rope_d, cfg.rope_theta)
    q_pe = apply_rope(q_pe, cos, sin)
    k_pe = apply_rope(k_pe, cos, sin)[:, :, 0, :]             # [B,1,rope_d]
    widx = jnp.mod(cache_pos, L)
    page_ids = jnp.take_along_axis(block_tables, (widx // P)[:, None],
                                   axis=1)[:, 0]
    offs = jnp.mod(widx, P)
    ckv_pages = paged_token_write(ckv_pages, c_kv[:, 0], page_ids, offs)
    kpe_pages = paged_token_write(kpe_pages, k_pe[:, 0], page_ids, offs)
    ckv = gather_pages(ckv_pages, block_tables)               # [B,L,rank]
    kpe = gather_pages(kpe_pages, block_tables)               # [B,L,rope_d]
    n_valid = jnp.minimum(cache_pos + 1, L)
    w_uk = params["w_uk"].reshape(rank, h, nope)
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
    scale = 1.0 / jnp.sqrt(nope + rope_d)
    scores = (jnp.einsum("bhr,blr->bhl", q_abs.astype(jnp.float32),
                         ckv.astype(jnp.float32))
              + jnp.einsum("bhd,bld->bhl", q_pe[:, 0].astype(jnp.float32),
                           kpe.astype(jnp.float32))) * scale
    valid = jnp.arange(L)[None, :] < n_valid[:, None]
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhl,blr->bhr", probs,
                     ckv.astype(jnp.float32)).astype(x.dtype)
    w_uv = params["w_uv"].reshape(rank, h, vhd)
    out = jnp.einsum("bhr,rhd->bhd", ctx, w_uv).reshape(b, 1, h * vhd)
    return jnp.dot(out, params["wo"]), ckv_pages, kpe_pages
