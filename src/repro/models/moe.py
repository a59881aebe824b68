"""Mixture-of-Experts FFN: token-choice top-k router + ``jax.lax.ragged_dot``.

FLOP-exact dispatch: token-expert pairs are sorted by expert id and fed
through ``ragged_dot`` against the stacked expert weights — no dense
[T, E, C] dispatch tensor.

Distribution: when a ``ShardingCtx`` is provided the FFN runs inside
``jax.shard_map`` with tokens sharded over (dp_axes + (tp_axis,)) and expert
weights gathered per device (baseline strategy; see DESIGN.md §5 and the
§Perf log for the expert-parallel alternative).  Routing and the ragged
matmuls are then fully local.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


class ShardingCtx(NamedTuple):
    """Mesh context threaded through model forward passes."""
    mesh: object                       # jax.sharding.Mesh
    dp_axes: tuple = ("data",)         # axes sharding the batch
    tp_axis: str = "model"             # axis sharding heads/ffn/experts
    seq_shard: bool = True             # shard seq over tp_axis inside MoE
    expert_parallel: bool = False      # expert-parallel MoE (psum combine)
    attn_sharding: str = "none"        # "auto": sequence-parallel attention
    fsdp_axes: tuple = ()              # axes the weights are fsdp-sharded on


def init_moe(key, cfg, dtype):
    e = cfg.moe
    d, ff = cfg.d_model, e.d_ff_expert
    ks = jax.random.split(key, 5)
    s, sf = d ** -0.5, ff ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, e.num_experts)) * s).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e.num_experts, d, ff)) * s).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e.num_experts, d, ff)) * s).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e.num_experts, ff, d)) * sf).astype(dtype),
    }
    if e.num_shared_experts:
        ksh = jax.random.split(ks[4], 3)
        ff_sh = ff * e.num_shared_experts
        p["shared"] = {
            "w_gate": (jax.random.normal(ksh[0], (d, ff_sh)) * s).astype(dtype),
            "w_up": (jax.random.normal(ksh[1], (d, ff_sh)) * s).astype(dtype),
            "w_down": (jax.random.normal(ksh[2], (ff_sh, d)) * sf).astype(dtype),
        }
    return p


def _local_moe(x2d, router, w_gate, w_up, w_down, top_k: int):
    """Token-choice top-k MoE over a local token slab.

    x2d: [T, d].  Returns ([T, d], router probs [T, E] f32).
    """
    t, d = x2d.shape
    n_experts = router.shape[1]
    logits = jnp.dot(x2d.astype(jnp.float32), router)          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)        # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    flat_expert = expert_idx.reshape(-1)                       # [T*k]
    order = jnp.argsort(flat_expert)                           # stable
    token_of = order // top_k                                  # source token
    xs = jnp.take(x2d, token_of, axis=0)                       # [T*k, d]
    group_sizes = jnp.bincount(flat_expert, length=n_experts).astype(jnp.int32)

    g = jax.lax.ragged_dot(xs, w_gate, group_sizes)
    u = jax.lax.ragged_dot(xs, w_up, group_sizes)
    h = jax.nn.silu(g) * u
    y = jax.lax.ragged_dot(h, w_down, group_sizes)             # [T*k, d]

    w = jnp.take(gate_vals.reshape(-1), order)[:, None].astype(y.dtype)
    out = jnp.zeros_like(x2d).at[token_of].add(y * w)
    return out, probs


def _local_moe_ep(x2d, router, w_gate, w_up, w_down, top_k: int,
                  tp_axis: str, num_experts: int, psum_axes=None):
    """Expert-parallel MoE shard (beyond-paper §Perf optimization).

    Runs inside shard_map with tokens REPLICATED over ``tp_axis`` and the
    expert weights SHARDED over it (w_*: [E_local, ...]).  Each device
    routes all tokens against the full router, computes only the pairs
    assigned to its local experts via ragged_dot (a zero dummy expert
    absorbs non-local pairs), and a psum over ``tp_axis`` combines the
    per-expert contributions — replacing the baseline's per-layer expert
    weight all-gather with one activation-sized all-reduce.
    """
    t, d = x2d.shape
    e_local = w_gate.shape[0]
    lo = jax.lax.axis_index(tp_axis) * e_local
    logits = jnp.dot(x2d.astype(jnp.float32), router)          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)        # [T, k]
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    flat_expert = expert_idx.reshape(-1)                       # [T*k] global
    local_id = flat_expert - lo
    in_range = (local_id >= 0) & (local_id < e_local)
    sort_key = jnp.where(in_range, local_id, e_local)          # dummy last
    order = jnp.argsort(sort_key)
    token_of = order // top_k
    xs = jnp.take(x2d, token_of, axis=0)
    group_sizes = jnp.bincount(sort_key, length=e_local + 1).astype(jnp.int32)

    pad = lambda w: jnp.concatenate(
        [w, jnp.zeros((1,) + w.shape[1:], w.dtype)], axis=0)
    g = jax.lax.ragged_dot(xs, pad(w_gate), group_sizes)
    u = jax.lax.ragged_dot(xs, pad(w_up), group_sizes)
    h = jax.nn.silu(g) * u
    y = jax.lax.ragged_dot(h, pad(w_down), group_sizes)        # [T*k, d]

    w = jnp.take(gate_vals.reshape(-1), order) * \
        jnp.take(in_range, order)
    out = jnp.zeros_like(x2d).at[token_of].add(
        y * w[:, None].astype(y.dtype))
    out = jax.lax.psum(out, psum_axes if psum_axes is not None else tp_axis)
    return out, probs


def load_balance_loss(probs: jax.Array, expert_idx_probs: Optional[jax.Array],
                      top_k: int) -> jax.Array:
    """Switch-style auxiliary load-balancing loss (f·P formulation)."""
    n_experts = probs.shape[-1]
    # fraction of router prob mass per expert
    p_mean = jnp.mean(probs, axis=0)
    # fraction of tokens whose argmax is each expert
    hard = jax.nn.one_hot(jnp.argmax(probs, axis=-1), n_experts)
    f_mean = jnp.mean(hard, axis=0)
    return n_experts * jnp.sum(f_mean * p_mean)


def moe_ffn(params, cfg, x, ctx: Optional[ShardingCtx] = None):
    """MoE FFN over x [B, S, D].  Returns (out, aux_loss f32 scalar)."""
    e = cfg.moe
    b, s, d = x.shape

    def body(xx, router, wg, wu, wd):
        bb, ss, _ = xx.shape
        out, probs = _local_moe(xx.reshape(bb * ss, d), router, wg, wu, wd,
                                e.top_k)
        aux = load_balance_loss(probs, None, e.top_k)
        return out.reshape(bb, ss, d), aux

    if ctx is None or ctx.mesh is None:
        out, aux = body(x, params["router"], params["w_gate"],
                        params["w_up"], params["w_down"])
    else:
        mesh = ctx.mesh
        dp = tuple(ctx.dp_axes) if isinstance(ctx.dp_axes, (tuple, list)) \
            else (ctx.dp_axes,)
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        tp_size = mesh.shape[ctx.tp_axis]
        batch_axes = dp if (dp_size > 1 and b % dp_size == 0) else ()
        seq_axis = ctx.tp_axis if (ctx.seq_shard and s > 1
                                   and s % tp_size == 0) else None
        if ctx.expert_parallel and e.num_experts % tp_size == 0:
            # Experts sharded over tp inside the shard_map; psum combines
            # per-expert contributions (see _local_moe_ep).  For SMALL token
            # counts (decode) with FSDP weights, the 2D variant additionally
            # keeps the ff dim sharded over the fsdp axes — the stored
            # layout — so NO weight movement happens at all; the psum then
            # runs over (tp + fsdp) axes on tiny activations.
            fsdp_axes = tuple(ctx.fsdp_axes or ())
            use_2d = bool(fsdp_axes) and b * s <= 4096
            if use_2d:
                xspec = P(None, None, None)
                wspec_gu = P(ctx.tp_axis, None, fsdp_axes)
                wspec_d = P(ctx.tp_axis, fsdp_axes, None)
                psum_axes = (ctx.tp_axis,) + fsdp_axes
                pmean_axes = ()
            else:
                xspec = P(batch_axes or None, None, None)
                wspec_gu = P(ctx.tp_axis, None, None)
                wspec_d = P(ctx.tp_axis, None, None)
                psum_axes = (ctx.tp_axis,)
                pmean_axes = batch_axes

            def smbody_ep(xx, router, wg, wu, wd):
                bb, ss, _ = xx.shape
                out, probs = _local_moe_ep(
                    xx.reshape(bb * ss, d), router, wg, wu, wd, e.top_k,
                    ctx.tp_axis, e.num_experts, psum_axes=psum_axes)
                aux = load_balance_loss(probs, None, e.top_k)
                if pmean_axes:
                    aux = jax.lax.pmean(aux, pmean_axes)
                return out.reshape(bb, ss, d), aux

            out, aux = jax.shard_map(
                smbody_ep, mesh=mesh,
                in_specs=(xspec, P(None, None), wspec_gu, wspec_gu, wspec_d),
                out_specs=(xspec, P()),
            )(x, params["router"], params["w_gate"], params["w_up"],
              params["w_down"])
        else:
            xspec = P(batch_axes or None, seq_axis, None)
            rep2, rep3 = P(None, None), P(None, None, None)
            pmean_axes = batch_axes + ((seq_axis,) if seq_axis else ())

            def smbody(xx, router, wg, wu, wd):
                out, aux = body(xx, router, wg, wu, wd)
                if pmean_axes:
                    aux = jax.lax.pmean(aux, pmean_axes)
                return out, aux

            out, aux = jax.shard_map(
                smbody, mesh=mesh,
                in_specs=(xspec, rep2, rep3, rep3, rep3),
                out_specs=(xspec, P()),
            )(x, params["router"], params["w_gate"], params["w_up"],
              params["w_down"])

    if e.num_shared_experts:
        sh = params["shared"]
        g = jnp.dot(x, sh["w_gate"])
        u = jnp.dot(x, sh["w_up"])
        out = out + jnp.dot(jax.nn.silu(g) * u, sh["w_down"])
    return out, aux.astype(jnp.float32)
