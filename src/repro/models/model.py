"""Unified decoder-only model covering all supported families.

A model is (init_params, forward).  The layer stack is driven by
``cfg.layer_pattern`` — attention (GQA or MLA) blocks, Mamba2 SSD blocks, or
a mix (hybrid).  MoE configs replace the dense FFN on non-dense layers.
Audio (MusicGen) models embed K codebooks and emit K logit heads; VLM
backbones accept precomputed ``embeds`` instead of token ids.

The forward pass is written against plain jnp ops so that XLA's SPMD
partitioner can shard it from the in/out shardings alone; the MoE FFN is the
one explicitly shard_mapped component (see moe.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import ssm as ssm_mod
from repro.models.cache import write_prefill, write_prefill_paged
from repro.models.config import ATTN, SSM, ModelConfig
from repro.models.layers import apply_mlp, apply_norm, init_mlp, init_norm
from repro.models.moe import ShardingCtx, init_moe, moe_ffn


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _is_moe_layer(cfg: ModelConfig, i: int) -> bool:
    return cfg.moe is not None and i not in cfg.moe.dense_layers


def _init_attn_block(key, cfg, i, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {"attn_norm": init_norm(cfg, cfg.d_model),
         "mlp_norm": init_norm(cfg, cfg.d_model)}
    if cfg.mla is not None:
        p["attn"] = attn.init_mla(k1, cfg, dtype)
    else:
        p["attn"] = attn.init_gqa(k1, cfg, dtype)
    if _is_moe_layer(cfg, i):
        p["moe"] = init_moe(k2, cfg, dtype)
    else:
        ff = cfg.d_ff
        if cfg.moe is not None and cfg.moe.d_ff_dense:
            ff = cfg.moe.d_ff_dense
        p["mlp"] = init_mlp(k3, cfg, cfg.d_model, ff, dtype)
    return p


def init_params(key, cfg: ModelConfig):
    dtype = _dtype(cfg)
    keys = jax.random.split(key, cfg.num_layers + 3)
    s = cfg.d_model ** -0.5
    nc = cfg.num_codebooks or 1
    if nc > 1:
        embed = (jax.random.normal(keys[0], (nc, cfg.vocab_size, cfg.d_model))
                 * s).astype(dtype)
    else:
        embed = (jax.random.normal(keys[0], (cfg.vocab_size, cfg.d_model))
                 * s).astype(dtype)
    params = {"embed": embed, "final_norm": init_norm(cfg, cfg.d_model),
              "layers": []}
    if not cfg.tie_embeddings:
        if nc > 1:
            params["lm_head"] = (jax.random.normal(
                keys[1], (nc, cfg.d_model, cfg.vocab_size)) * s).astype(dtype)
        else:
            params["lm_head"] = (jax.random.normal(
                keys[1], (cfg.d_model, cfg.vocab_size)) * s).astype(dtype)
    shared_block = None
    for i, kind in enumerate(cfg.layer_pattern):
        k = keys[2 + i]
        if kind == SSM:
            params["layers"].append(
                {"norm": init_norm(cfg, cfg.d_model),
                 "mamba": ssm_mod.init_mamba2(k, cfg, dtype)})
        else:
            if cfg.shared_attn_weights:
                if shared_block is None:
                    shared_block = _init_attn_block(k, cfg, i, dtype)
                    params["shared_block"] = shared_block
                # empty dict marker (no leaves): weights live in shared_block
                params["layers"].append({})
            else:
                params["layers"].append(_init_attn_block(k, cfg, i, dtype))
    return params


def embed_tokens(params, cfg, tokens):
    nc = cfg.num_codebooks or 1
    if nc > 1:
        # tokens [B, S, K]
        embs = [jnp.take(params["embed"][k], tokens[..., k], axis=0)
                for k in range(nc)]
        return sum(embs)
    return jnp.take(params["embed"], tokens, axis=0)


def lm_head(params, cfg, x):
    nc = cfg.num_codebooks or 1
    if cfg.tie_embeddings:
        if nc > 1:
            return jnp.einsum("bsd,kvd->bskv", x, params["embed"])
        return jnp.dot(x, params["embed"].T)
    if nc > 1:
        return jnp.einsum("bsd,kdv->bskv", x, params["lm_head"])
    return jnp.dot(x, params["lm_head"])


def _attn_block_full(block, cfg, x, positions, ctx, attn_kernel=None):
    with jax.named_scope("attn_proj"):
        xin = apply_norm(x, block["attn_norm"], cfg)
    h, kv_out = (attn.mla_full if cfg.mla is not None else attn.gqa_full)(
        block["attn"], cfg, xin, positions, ctx=ctx, attn_kernel=attn_kernel)
    x = x + h
    aux = jnp.float32(0.0)
    with jax.named_scope("mlp"):
        y = apply_norm(x, block["mlp_norm"], cfg)
        if "moe" in block:
            y, aux = moe_ffn(block["moe"], cfg, y, ctx)
        else:
            y = apply_mlp(y, block["mlp"], cfg)
    return x + y, kv_out, aux


def _attn_block_decode(block, cfg, x, positions, layer_cache, cache_pos, ctx):
    xin = apply_norm(x, block["attn_norm"], cfg)
    if cfg.mla is not None:
        h, ckv, kpe = attn.mla_decode(block["attn"], cfg, xin, positions,
                                      layer_cache["ckv"], layer_cache["kpe"],
                                      cache_pos)
        new_cache = {"ckv": ckv, "kpe": kpe}
    else:
        h, new_cache = attn.gqa_decode(
            block["attn"], cfg, xin, positions,
            layer_cache["k"], layer_cache["v"], cache_pos,
            k_scale=layer_cache.get("k_scale"),
            v_scale=layer_cache.get("v_scale"))
    x = x + h
    y = apply_norm(x, block["mlp_norm"], cfg)
    if "moe" in block:
        y, _ = moe_ffn(block["moe"], cfg, y, ctx)
    else:
        y = apply_mlp(y, block["mlp"], cfg)
    return x + y, new_cache


def _default_positions(cfg, batch, seq, offset=0):
    pos = offset + jnp.arange(seq, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (batch, seq))
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[..., None], (batch, seq, 3))
    return pos


def forward_full(params, cfg: ModelConfig, *, tokens=None, embeds=None,
                 positions=None, cache=None,
                 ctx: Optional[ShardingCtx] = None, remat: bool = False,
                 last_only: bool = False, attn_kernel: Optional[str] = None):
    """Train / prefill pass over a whole sequence.

    ``last_only`` applies the LM head to the final position only (prefill
    path — avoids materializing [B, S, V] logits).  ``attn_kernel`` (a
    ``kernels.ops`` mode) routes GQA attention through the flash kernel
    dispatcher; None keeps the einsum path.
    Returns (logits, cache_or_None, aux_loss).
    """
    x = embeds if embeds is not None else embed_tokens(params, cfg, tokens)
    b, s = x.shape[0], x.shape[1]
    if positions is None:
        positions = _default_positions(cfg, b, s)
    aux_total = jnp.float32(0.0)

    # Full sequence-parallelism (§Perf): in "auto" mode keep the residual
    # stream sharded (batch over dp, seq over tp) BETWEEN blocks too —
    # norms/MLPs are elementwise over seq, so only attention k/v gathers
    # remain, removing the per-layer gather↔scatter ping-pong.
    from repro.models.attention import _constrain, _seq_parallel_wanted
    seq_par = _seq_parallel_wanted(cfg, ctx, s) and not cfg.ssm_layers
    dpb = None
    if seq_par:
        dp_size = 1
        for a in ctx.dp_axes:
            dp_size *= ctx.mesh.shape[a]
        dpb = ctx.dp_axes if b % dp_size == 0 else None
        x = _constrain(x, ctx, dpb, ctx.tp_axis, None)

    for i, kind in enumerate(cfg.layer_pattern):
        block = params["layers"][i] or params.get("shared_block")

        if kind == SSM:
            def layer_fn(xx, blk):
                h, state = ssm_mod.mamba2_full(
                    blk["mamba"], cfg, apply_norm(xx, blk["norm"], cfg))
                return xx + h, state, jnp.float32(0.0)
        else:
            def layer_fn(xx, blk):
                return _attn_block_full(blk, cfg, xx, positions, ctx,
                                        attn_kernel)

        if remat:
            layer_fn = jax.checkpoint(layer_fn)
        x, kv_out, aux = layer_fn(x, block)
        if seq_par:
            x = _constrain(x, ctx, dpb, ctx.tp_axis, None)
        aux_total = aux_total + aux
        if cache is not None:
            cache = write_prefill(cache, i, kv_out, cfg)

    if last_only:
        x = x[:, -1:]
    x = apply_norm(x, params["final_norm"], cfg)
    logits = lm_head(params, cfg, x)
    if cache is not None:
        cache["pos"] = cache["pos"] + s
    return logits, cache, aux_total / max(1, len(cfg.attn_layers))


def forward_decode(params, cfg: ModelConfig, *, tokens=None, embeds=None,
                   positions=None, cache=None,
                   ctx: Optional[ShardingCtx] = None):
    """One-token decode step. tokens: [B, 1] (or [B,1,K] audio).

    Returns (logits, new_cache).
    """
    assert cache is not None
    x = embeds if embeds is not None else embed_tokens(params, cfg, tokens)
    b = x.shape[0]
    cache_pos = cache["pos"]
    if positions is None:
        pos = cache_pos[:, None]
        if cfg.mrope_sections:
            pos = jnp.broadcast_to(pos[..., None], (b, 1, 3))
        positions = pos
    new_layers = []
    for i, kind in enumerate(cfg.layer_pattern):
        block = params["layers"][i] or params.get("shared_block")
        layer_cache = cache["layers"][i]
        if kind == SSM:
            h, conv, st = ssm_mod.mamba2_decode(
                block["mamba"], cfg, apply_norm(x, block["norm"], cfg),
                layer_cache["conv"], layer_cache["ssm"])
            x = x + h
            new_layers.append({"conv": conv, "ssm": st})
        else:
            x, new_lc = _attn_block_decode(block, cfg, x, positions,
                                           layer_cache, cache_pos, ctx)
            new_layers.append(new_lc)
    x = apply_norm(x, params["final_norm"], cfg)
    logits = lm_head(params, cfg, x)
    return logits, {"pos": cache_pos + 1, "layers": new_layers}


def forward_decode_paged(params, cfg: ModelConfig, *, tokens=None,
                         embeds=None, positions=None, cache=None,
                         ctx: Optional[ShardingCtx] = None,
                         kernel_mode: str = "ref"):
    """One-token decode step against a paged KV pool.

    ``cache`` is an :func:`repro.models.cache.init_paged_cache` pytree:
    attention layers hold shared page arrays plus per-slot block tables;
    SSM layers keep their per-slot state.  New tokens are written in
    place into their pages (O(B) scatter) and attention reads through
    the block table in ``kernel_mode`` (see ``kernels.ops``).  Returns
    (logits, new_cache).
    """
    assert cache is not None
    with jax.named_scope("embed"):
        x = embeds if embeds is not None \
            else embed_tokens(params, cfg, tokens)
    b = x.shape[0]
    cache_pos = cache["pos"]
    block_tables = cache["block_tables"]
    if positions is None:
        pos = cache_pos[:, None]
        if cfg.mrope_sections:
            pos = jnp.broadcast_to(pos[..., None], (b, 1, 3))
        positions = pos
    new_layers = []
    for i, kind in enumerate(cfg.layer_pattern):
        block = params["layers"][i] or params.get("shared_block")
        layer_cache = cache["layers"][i]
        if kind == SSM:
            h, conv, st = ssm_mod.mamba2_decode(
                block["mamba"], cfg, apply_norm(x, block["norm"], cfg),
                layer_cache["conv"], layer_cache["ssm"])
            x = x + h
            new_layers.append({"conv": conv, "ssm": st})
            continue
        with jax.named_scope("attn_proj"):
            xin = apply_norm(x, block["attn_norm"], cfg)
        if cfg.mla is not None:
            h, ckv, kpe = attn.mla_decode_paged(
                block["attn"], cfg, xin, positions, layer_cache["ckv"],
                layer_cache["kpe"], block_tables, cache_pos)
            new_lc = {"ckv": ckv, "kpe": kpe}
        else:
            h, new_lc = attn.gqa_decode_paged(
                block["attn"], cfg, xin, positions, layer_cache,
                block_tables, cache_pos, kernel_mode=kernel_mode)
        x = x + h
        with jax.named_scope("mlp"):
            y = apply_norm(x, block["mlp_norm"], cfg)
            if "moe" in block:
                y, _ = moe_ffn(block["moe"], cfg, y, ctx)
            else:
                y = apply_mlp(y, block["mlp"], cfg)
        x = x + y
        new_layers.append(new_lc)
    with jax.named_scope("head"):
        x = apply_norm(x, params["final_norm"], cfg)
        logits = lm_head(params, cfg, x)
    return logits, {"pos": cache_pos + 1, "block_tables": block_tables,
                    "layers": new_layers}


def forward_prefill_paged(params, cfg: ModelConfig, *, tokens=None,
                          embeds=None, positions=None, cache=None,
                          slot=0, length=None,
                          ctx: Optional[ShardingCtx] = None):
    """Whole-prompt prefill of ONE request written *in place* into
    ``slot``'s pages of the shared pool (no per-prefill full-length
    cache allocation, no O(pool) commit copy — each layer's K/V is an
    O(prompt) scatter through the slot's block table; padded positions
    land on the null page).

    tokens: [1, Lpad]; ``length``: actual prompt length.  Returns
    (last-token logits [V], new_cache).
    """
    assert cache is not None
    with jax.named_scope("embed"):
        x = embeds if embeds is not None \
            else embed_tokens(params, cfg, tokens)
    b, s = x.shape[0], x.shape[1]
    if length is None:
        length = s
    if positions is None:
        positions = _default_positions(cfg, b, s)
    for i, kind in enumerate(cfg.layer_pattern):
        block = params["layers"][i] or params.get("shared_block")
        if kind == SSM:
            h, state = ssm_mod.mamba2_full(
                block["mamba"], cfg, apply_norm(x, block["norm"], cfg))
            x = x + h
            kv_out = state
        else:
            x, kv_out, _ = _attn_block_full(block, cfg, x, positions, ctx)
        with jax.named_scope("kv_write"):
            cache = write_prefill_paged(cache, i, kv_out, cfg, slot, length)
    with jax.named_scope("head"):
        x = apply_norm(x, params["final_norm"], cfg)
        logits = lm_head(params, cfg, x[:, length - 1][:, None])
    cache["pos"] = cache["pos"].at[slot].set(length)
    return logits[0, 0], cache


def forward_chunk_paged(params, cfg: ModelConfig, *, tokens=None,
                        embeds=None, cache=None, slot=0, length=None,
                        ctx: Optional[ShardingCtx] = None):
    """Chunked-prefill step for ONE slot against the paged pool
    (Sarathi-style).  The chunk attends to the slot's gathered prefix
    pages plus itself, then is scattered into its pages in place.

    The chunk starts at ``cache["pos"][slot]`` — which need not be 0:
    a request aliasing a cached prefix (shared-prefix KV reuse) presets
    ``pos`` to the cached length and prefills only its unique suffix
    through this path.

    tokens: [1, C]; ``length`` (static or traced; default C) is the
    number of valid rows — padded power-of-two suffix buckets reuse one
    compilation per bucket.  Padded rows are never written to pages and
    never attended by valid queries; ``length < C`` is only meaningful
    for attention-only archs (SSM state consumes all C rows in order).
    Returns (valid-final logits [1,1,V], new_cache).
    """
    assert cache is not None
    assert cfg.mla is None, "chunked prefill: MLA not supported"
    x = embeds if embeds is not None else embed_tokens(params, cfg, tokens)
    b, c = x.shape[0], x.shape[1]
    if length is None:
        length = c
    length = jnp.asarray(length, jnp.int32)
    pos0 = cache["pos"][slot]
    bt = jax.lax.dynamic_slice_in_dim(cache["block_tables"], slot, 1)
    positions = pos0 + jnp.arange(c, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(positions, (b, c))
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(positions[..., None], (b, c, 3))
    for i, kind in enumerate(cfg.layer_pattern):
        block = params["layers"][i] or params.get("shared_block")
        layer_cache = cache["layers"][i]
        if kind == SSM:
            h, (conv, st) = ssm_mod.mamba2_full(
                block["mamba"], cfg, apply_norm(x, block["norm"], cfg),
                conv_state=jax.lax.dynamic_slice_in_dim(
                    layer_cache["conv"], slot, 1).astype(x.dtype),
                ssm_state=jax.lax.dynamic_slice_in_dim(
                    layer_cache["ssm"], slot, 1))
            x = x + h
            cache["layers"][i] = {
                "conv": layer_cache["conv"].at[slot].set(
                    conv[0].astype(layer_cache["conv"].dtype)),
                "ssm": layer_cache["ssm"].at[slot].set(st[0])}
        else:
            xin = apply_norm(x, block["attn_norm"], cfg)
            h, new_lc = attn.gqa_continue_paged(
                block["attn"], cfg, xin, positions, layer_cache, bt, pos0,
                n=length)
            x = x + h
            y = apply_norm(x, block["mlp_norm"], cfg)
            if "moe" in block:
                y, _ = moe_ffn(block["moe"], cfg, y, ctx)
            else:
                y = apply_mlp(y, block["mlp"], cfg)
            x = x + y
            cache["layers"][i] = new_lc
    x = apply_norm(x, params["final_norm"], cfg)
    x = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    logits = lm_head(params, cfg, x)
    cache["pos"] = cache["pos"].at[slot].add(length)
    return logits, cache


def forward_chunk(params, cfg: ModelConfig, *, tokens=None, embeds=None,
                  cache=None, ctx: Optional[ShardingCtx] = None):
    """Chunked-prefill step: process a chunk of C tokens against a cache
    already holding ``cache["pos"]`` tokens (Sarathi-style).  Supports
    attention (GQA) and SSM layers; MLA archs use whole-sequence prefill.

    Returns (logits for the chunk's last position [B,1,V], cache).
    """
    assert cache is not None
    assert cfg.mla is None, "chunked prefill: MLA not supported"
    x = embeds if embeds is not None else embed_tokens(params, cfg, tokens)
    b, c = x.shape[0], x.shape[1]
    # positions from the cache pointer (uniform across batch by contract)
    pos0 = cache["pos"][0]
    positions = pos0 + jnp.arange(c, dtype=jnp.int32)[None, :]
    positions = jnp.broadcast_to(positions, (b, c))
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(positions[..., None], (b, c, 3))
    new_layers = []
    for i, kind in enumerate(cfg.layer_pattern):
        block = params["layers"][i] or params.get("shared_block")
        layer_cache = cache["layers"][i]
        if kind == SSM:
            h, (conv, st) = ssm_mod.mamba2_full(
                block["mamba"], cfg, apply_norm(x, block["norm"], cfg),
                conv_state=layer_cache["conv"].astype(x.dtype),
                ssm_state=layer_cache["ssm"])
            x = x + h
            new_layers.append({"conv": conv.astype(
                layer_cache["conv"].dtype), "ssm": st})
        else:
            xin = apply_norm(x, block["attn_norm"], cfg)
            h, kc, vc = attn.gqa_continue(
                block["attn"], cfg, xin, positions,
                layer_cache["k"], layer_cache["v"], pos0)
            x = x + h
            y = apply_norm(x, block["mlp_norm"], cfg)
            if "moe" in block:
                y, _ = moe_ffn(block["moe"], cfg, y, ctx)
            else:
                y = apply_mlp(y, block["mlp"], cfg)
            x = x + y
            new_layers.append({"k": kc, "v": vc})
    x = apply_norm(x, params["final_norm"], cfg)
    logits = lm_head(params, cfg, x[:, -1:])
    return logits, {"pos": cache["pos"] + c, "layers": new_layers}
