"""Paged flash-decode — Pallas TPU kernel for single-token decode
attention through a block table (vLLM-style PagedAttention).

The KV cache is a shared pool of pages laid out kv-head-major,
``[kv, num_blocks, block_size, hd]`` (the layout of JAX's own TPU
paged-attention kernel); each sequence owns a row of page ids (its
*block table*).  One grid step reads one ``[block_size, hd]`` page of
one kv head: the last two block dims are the page's own, so the block
meets the TPU's (8, 128) tiling for every block size that is a multiple
of 8 (16 for bf16) and any head_dim that is a multiple of 128.  The
innermost sequential grid dimension walks the sequence's logical pages:
the scalar-prefetched block table drives the ``BlockSpec`` index map,
so each step DMAs exactly one live page from HBM into VMEM — HBM
traffic is priced by live tokens, not by the pool or the slot's
worst-case length.  Online-softmax carry (max / denom / accumulator)
lives in 2-D VMEM scratch; all q heads sharing a kv head are processed
together as a ``[group, hd]`` tile, exactly like the dense
``decode_attention`` kernel this extends.

Masking is by *token id* on the slot's logical ring (length
``pages_per_seq * block_size``): ring slot ``s`` holds token
``t_s = len-1 - mod(len-1-s, L)`` which is masked when negative (not yet
written) or outside the sliding window.  This makes the kernel correct
for windowed (ring) slots whose ring length was rounded up to whole
blocks.  Fully-dead pages are skipped with ``pl.when``; the caller must
clamp their table entries to a valid page id (see the wrapper).

``decode_attention_paged_q8`` is the int8-KV variant: pages are int8
with per-(token, head) bf16 scales ``[kv, num_blocks, block_size, 1]``,
dequantized in VMEM right before the MXU contractions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mask_scores(s, ln, k_start, L, window):
    """Token-id ring mask for a [g, block] score tile starting at ring
    slot ``k_start``; ``ln`` = tokens written so far (incl. current)."""
    s_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    t_s = ln - 1 - jnp.mod(ln - 1 - s_idx, L)
    valid = t_s >= 0
    if window > 0:
        valid &= t_s > ln - 1 - window
    return jnp.where(valid, s, NEG_INF)


def _paged_softmax_step(load_kv, lengths_ref, q_ref, o_ref, m_scr, l_scr,
                        acc_scr, *, scale, block_size, num_pages, window):
    """Shared per-page online-softmax body: init the carry on the first
    page, attend the current page's (dequantized) K/V tile, emit the
    normalized output on the last.  ``load_kv()`` returns the page's
    float32 [P, hd] k and v tiles — the only point the float and int8
    kernels differ."""
    bi = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ln = lengths_ref[bi]
    L = num_pages * block_size
    n_valid = jnp.minimum(ln, L)
    k_start = pi * block_size

    @pl.when(k_start < n_valid)
    def _body():
        q = q_ref[...].astype(jnp.float32) * scale             # [g, hd]
        k, v = load_kv()                                       # [P, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask_scores(s, ln, k_start, L, window)            # [g, P]
        m_prev = m_scr[...]                                    # [g, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(pi == num_pages - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _kernel(bt_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, **static):
    def load_kv():
        return (k_ref[...].astype(jnp.float32),
                v_ref[...].astype(jnp.float32))
    _paged_softmax_step(load_kv, lengths_ref, q_ref, o_ref, m_scr, l_scr,
                        acc_scr, **static)


def _kernel_q8(bt_ref, lengths_ref, q_ref, k_ref, ks_ref, v_ref, vs_ref,
               o_ref, m_scr, l_scr, acc_scr, **static):
    def load_kv():
        # dequantize the int8 page in VMEM right before the contractions
        return (k_ref[...].astype(jnp.float32)
                * ks_ref[...].astype(jnp.float32),
                v_ref[...].astype(jnp.float32)
                * vs_ref[...].astype(jnp.float32))
    _paged_softmax_step(load_kv, lengths_ref, q_ref, o_ref, m_scr, l_scr,
                        acc_scr, **static)


def _safe_tables(block_tables, lengths, block_size, num_blocks):
    """Clamp table entries of fully-dead pages to the null page so their
    prefetch-driven DMAs stay in-bounds (the kernel skips their math)."""
    num_pages = block_tables.shape[1]
    L = num_pages * block_size
    live = (jnp.arange(num_pages, dtype=jnp.int32)[None, :] * block_size) \
        < jnp.minimum(lengths, L)[:, None]
    bt = jnp.clip(block_tables, 0, num_blocks - 1)
    return jnp.where(live, bt, 0).astype(jnp.int32)


def _paged_call(kernel, name, q, pages, block_tables, lengths, window,
                interpret):
    """Build and run the paged pallas_call, named ``name`` in traces.
    ``pages`` is the kernel's page-array operands in order (k, v or k,
    k_scale, v, v_scale), all ``[kv, N, P, last]``."""
    b, h, hd = q.shape
    kv, n_blocks, P = pages[0].shape[:3]
    num_pages = block_tables.shape[1]
    g = h // kv
    qg = q.reshape(b, kv, g, hd)
    bt = _safe_tables(block_tables, lengths, P, n_blocks)

    def page_spec(arr):
        return pl.BlockSpec((None, None, P, arr.shape[-1]),
                            lambda bi, hi, pi, bt, ln:
                            (hi, bt[bi, pi], 0, 0))
    head_spec = pl.BlockSpec((None, None, g, hd),
                             lambda bi, hi, pi, bt, ln: (bi, hi, 0, 0))
    out = pl.pallas_call(
        functools.partial(kernel, scale=hd ** -0.5, block_size=P,
                          num_pages=num_pages, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv, num_pages),
            in_specs=[head_spec] + [page_spec(a) for a in pages],
            out_specs=head_spec,
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
        name=name,
    )(bt, lengths, qg, *pages)
    return out.reshape(b, h, hd)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def decode_attention_paged(q, k_pages, v_pages, block_tables, lengths, *,
                           window: int = 0, interpret: bool = False):
    """q: [B,H,hd]; pages: [KV,N,P,hd]; block_tables: [B,pages_per_seq]
    int32; lengths: [B] int32 (context length incl. the current token)
    -> [B,H,hd]."""
    return _paged_call(_kernel, "decode_attention_paged", q,
                       (k_pages, v_pages), block_tables, lengths, window,
                       interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def decode_attention_paged_q8(q, k_pages, k_scale, v_pages, v_scale,
                              block_tables, lengths, *, window: int = 0,
                              interpret: bool = False):
    """int8 pages [KV,N,P,hd] + bf16 scales [KV,N,P,1]; else as
    :func:`decode_attention_paged`."""
    return _paged_call(_kernel_q8, "decode_attention_paged_q8", q,
                       (k_pages, k_scale, v_pages, v_scale), block_tables,
                       lengths, window, interpret)
