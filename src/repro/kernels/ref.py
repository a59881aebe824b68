"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                        scale: float | None = None):
    """q: [B,S,H,hd]; k,v: [B,S,KV,hd] (KV divides H). -> [B,S,H,hd]."""
    b, s, h, hd = q.shape
    kv = k.shape[2]
    rep = h // kv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else hd ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, n_valid, *,
                         scale: float | None = None):
    """q: [B,H,hd]; caches: [B,L,KV,hd]; n_valid: [B] int32. -> [B,H,hd]."""
    b, h, hd = q.shape
    kv = k_cache.shape[2]
    rep = h // kv
    if rep > 1:
        k_cache = jnp.repeat(k_cache, rep, axis=2)
        v_cache = jnp.repeat(v_cache, rep, axis=2)
    scale = scale if scale is not None else hd ** -0.5
    scores = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    L = k_cache.shape[1]
    valid = jnp.arange(L)[None, :] < n_valid[:, None]
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhl,blhd->bhd", p,
                      v_cache.astype(jnp.float32)).astype(q.dtype)


def decode_attention_paged_ref(q, k_pages, v_pages, block_tables, lengths,
                               *, window: int = 0,
                               scale: float | None = None):
    """Paged flash-decode oracle: gather live pages into the logical
    [B, L, kv, hd] view, then token-id ring masking.

    q: [B,H,hd]; pages: [KV,N,P,hd]; block_tables: [B,pages_per_seq];
    lengths: [B] int32 (context length incl. current token). -> [B,H,hd].
    """
    b, h, hd = q.shape
    kv, _, P, _ = k_pages.shape
    num_pages = block_tables.shape[1]
    L = num_pages * P

    def gather(pages):                      # -> [B, L, kv, hd]
        return jnp.moveaxis(pages[:, block_tables], 0, 3).reshape(
            b, L, kv, hd)
    k_cache = gather(k_pages)
    v_cache = gather(v_pages)
    rep = h // kv
    if rep > 1:
        k_cache = jnp.repeat(k_cache, rep, axis=2)
        v_cache = jnp.repeat(v_cache, rep, axis=2)
    scale = scale if scale is not None else hd ** -0.5
    scores = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) * scale
    # ring slot s holds token t_s = len-1 - mod(len-1-s, L); mask slots
    # not yet written (t_s < 0) and, for windowed archs, evicted tokens
    ln = lengths[:, None]
    s_idx = jnp.arange(L, dtype=jnp.int32)[None, :]
    t_s = ln - 1 - jnp.mod(ln - 1 - s_idx, L)
    valid = t_s >= 0
    if window > 0:
        valid &= t_s > ln - 1 - window
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhl,blhd->bhd", p,
                      v_cache.astype(jnp.float32)).astype(q.dtype)


def ssd_ref(x, dt, A, B, C, chunk: int, init_state=None):
    """Sequential (non-chunked) SSD recurrence oracle.

    x: [b,s,nh,hd]; dt: [b,s,nh]; A: [nh]; B, C: [b,s,ds].
    Returns (y [b,s,nh,hd], final_state [b,nh,hd,ds]).
    """
    b, s, nh, hd = x.shape
    ds = B.shape[-1]
    f32 = jnp.float32
    x, dt, B, C = (a.astype(f32) for a in (x, dt, B, C))
    state = (jnp.zeros((b, nh, hd, ds), f32) if init_state is None
             else init_state.astype(f32))

    def step(state, inp):
        xt, dtt, Bt, Ct = inp          # [b,nh,hd], [b,nh], [b,ds], [b,ds]
        decay = jnp.exp(dtt * A[None, :])
        upd = jnp.einsum("bh,bhp,bd->bhpd", dtt, xt, Bt)
        state = state * decay[:, :, None, None] + upd
        y = jnp.einsum("bhpd,bd->bhp", state, Ct)
        return state, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(B, 1, 0), jnp.moveaxis(C, 1, 0))
    final, ys = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(ys, 0, 1), final
