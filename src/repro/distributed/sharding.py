"""PartitionSpec rules for params, optimizer state, inputs and caches.

Mesh axes:
  * ``model`` (tp) — shards attention heads, FFN width, experts, vocab.
  * ``data`` / ``pod`` (dp) — shard the batch; in FSDP mode they also shard
    the non-tp dimension of every large weight (ZeRO-3 style).

Rules are name+shape driven over the params pytree produced by
``init_params`` — one place to read the whole distribution strategy.

SSM blocks: the Mamba2 in_proj concatenates (z | x | B | C | dt) whose
boundaries do not align with a 16-way column shard, so SSM weights shard
over the FSDP axis only (noted in DESIGN.md §5); SSM activations are data
parallel.  Attention/MoE layers carry the tensor-parallel dimension.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.moe import ShardingCtx


@dataclasses.dataclass(frozen=True)
class ParallelismConfig:
    tp_axis: str = "model"
    dp_axes: Tuple[str, ...] = ("data",)     # ("pod","data") multi-pod
    fsdp: bool = True                         # shard weights over dp too
    # shard the KV-cache sequence dim over tp when heads cannot shard
    seq_sharded_cache: bool = True
    # MoE expert-parallel all-to-all instead of weight gathering (§Perf)
    expert_parallel: bool = False
    # "auto": sequence-parallel attention activations (§Perf)
    attn_sharding: str = "none"

    @property
    def fsdp_spec(self):
        return self.dp_axes if self.fsdp else None


def _div(n: int, mesh: Mesh, axes) -> bool:
    """Is n divisible by the product of the named mesh axes?"""
    if axes is None:
        return False
    if isinstance(axes, str):
        axes = (axes,)
    size = int(np.prod([mesh.shape[a] for a in axes]))
    return n % size == 0 and n >= size


def _pspec(shape, mesh, par, *wants):
    """Build a PartitionSpec assigning wants[i] to dim i when divisible."""
    spec = []
    for dim, want in zip(shape, wants):
        if want is not None and _div(dim, mesh, want):
            spec.append(want)
        else:
            spec.append(None)
    return P(*spec)


def param_specs(params, cfg: ModelConfig, mesh: Mesh,
                par: ParallelismConfig):
    """PartitionSpec pytree matching the params structure."""
    tp = par.tp_axis
    fs = par.fsdp_spec

    def attn_block(block):
        out = {}
        for k, v in block.items():
            if k in ("attn_norm", "mlp_norm"):
                out[k] = jax.tree.map(lambda a: P(), v)
            elif k == "attn":
                out[k] = {}
                for name, w in v.items():
                    if name == "wq":
                        out[k][name] = _pspec(w.shape, mesh, par, fs, tp)
                    elif name in ("wk", "wv"):
                        out[k][name] = _pspec(w.shape, mesh, par, fs, tp)
                    elif name == "wo":
                        out[k][name] = _pspec(w.shape, mesh, par, tp, fs)
                    elif name in ("w_dkv", "w_krope"):
                        out[k][name] = _pspec(w.shape, mesh, par, fs, None)
                    elif name in ("w_uk", "w_uv"):
                        out[k][name] = _pspec(w.shape, mesh, par, None, tp)
                    else:               # q_norm, k_norm, kv_norm
                        out[k][name] = P()
            elif k == "mlp":
                out[k] = {n: _pspec(w.shape, mesh, par,
                                    *( (fs, tp) if n != "w_down" else (tp, fs)))
                          for n, w in v.items()}
            elif k == "moe":
                out[k] = {}
                for n, w in v.items():
                    if n == "router":
                        out[k][n] = _pspec(w.shape, mesh, par, fs, None)
                    elif n == "shared":
                        out[k][n] = {m: _pspec(x.shape, mesh, par,
                                               *((fs, tp) if m != "w_down"
                                                 else (tp, fs)))
                                     for m, x in w.items()}
                    elif n == "w_down":   # [E, F, D]
                        # fsdp on the ff dim: aligns with 2D expert-parallel
                        # decode (zero weight movement; §Perf iteration 5)
                        out[k][n] = _pspec(w.shape, mesh, par, tp, fs, None)
                    else:                 # w_gate / w_up [E, D, F]
                        out[k][n] = _pspec(w.shape, mesh, par, tp, None, fs)
            else:
                out[k] = jax.tree.map(lambda a: P(), v)
        return out

    def ssm_block(block):
        out = {"norm": jax.tree.map(lambda a: P(), block["norm"]), "mamba": {}}
        for n, w in block["mamba"].items():
            if w.ndim == 2:
                out["mamba"][n] = _pspec(w.shape, mesh, par, fs, None)
            else:
                out["mamba"][n] = P()
        return out

    specs = {}
    emb = params["embed"]
    # Embeddings shard on the vocab dim ONLY (never FSDP on d_model):
    # row-sharding the lm_head over the data axis makes GSPMD replicate the
    # batch and all-reduce full [B,S,V] logits (§Perf iteration 3) — the
    # tp-sharded table is small enough to keep resident.
    if emb.ndim == 3:      # audio [K, V, D]
        specs["embed"] = _pspec(emb.shape, mesh, par, None, tp, None)
    else:
        specs["embed"] = _pspec(emb.shape, mesh, par, tp, None)
    if "lm_head" in params:
        lh = params["lm_head"]
        if lh.ndim == 3:
            specs["lm_head"] = _pspec(lh.shape, mesh, par, None, None, tp)
        else:
            specs["lm_head"] = _pspec(lh.shape, mesh, par, None, tp)
    specs["final_norm"] = jax.tree.map(lambda a: P(), params["final_norm"])
    if "shared_block" in params:
        specs["shared_block"] = attn_block(params["shared_block"])
    specs["layers"] = []
    for layer in params["layers"]:
        if not layer:
            specs["layers"].append({})
        elif "mamba" in layer:
            specs["layers"].append(ssm_block(layer))
        else:
            specs["layers"].append(attn_block(layer))
    return specs


def cache_specs(cache_shapes, cfg: ModelConfig, mesh: Mesh,
                par: ParallelismConfig, batch: int):
    """PartitionSpec pytree for a decode cache (from cache_spec shapes).

    Handles both layouts:
      * dense per-slot ``[B, L, kv, hd]`` caches (``init_cache``);
      * block-paged pools (``init_paged_cache``) — detected by the presence
        of ``block_tables`` in the shapes pytree.  Kv-head-major page
        arrays ``[kv, num_blocks, block_size, hd]`` shard on the kv-head
        axis (dim 0) when the head count divides the tp axis; ``pos`` and
        ``block_tables`` stay replicated so the host-side BlockPool,
        prefix-reuse, and CoW logic never see a sharded array.
    """
    if "block_tables" in cache_shapes:
        return _paged_cache_specs(cache_shapes, mesh, par)
    tp = par.tp_axis
    dp = par.dp_axes
    batch_ok = _div(batch, mesh, dp)
    bspec = dp if batch_ok else None

    def layer_spec(layer):
        out = {}
        for k, v in layer.items():
            if k in ("k", "v", "k_scale", "v_scale"):  # [B, L, kv, hd|1]
                heads = v.shape[2]
                # prefer kv-head sharding; fall back to sequence sharding
                # (kv heads rarely divide a 16-way tp axis)
                hspec = tp if _div(heads, mesh, tp) else None
                seq = tp if (hspec is None and par.seq_sharded_cache and
                             _div(v.shape[1], mesh, tp)) else None
                out[k] = P(bspec, seq, hspec, None)
            elif k in ("ckv", "kpe"):  # [B, L, rank]
                seq = tp if (par.seq_sharded_cache and
                             _div(v.shape[1], mesh, tp)) else None
                out[k] = P(bspec, seq, None)
            elif k == "conv":          # [B, K-1, C]
                out[k] = P(bspec, None, None)
            elif k == "ssm":           # [B, nh, hd, ds]
                out[k] = P(bspec, None, None, None)
        return out

    return {"pos": P(bspec),
            "layers": [layer_spec(l) for l in cache_shapes["layers"]]}


def _paged_cache_specs(cache_shapes, mesh: Mesh, par: ParallelismConfig):
    """Specs for the block-paged pool layout (see cache_specs docstring).

    GQA kv-head groups stay whole per shard: sharding dim 0 of
    ``[kv, num_blocks, block_size, hd]`` by the tp axis puts kv/tp full
    heads on each device, and the query heads of each group shard the
    same way through ``wq``'s column shard — no cross-device attention.
    Per-slot SSM state (``conv``/``ssm``, leading dim = max_slots) and all
    host-consulted arrays (``pos``, ``block_tables``) remain replicated.
    """
    tp = par.tp_axis

    def layer_spec(layer):
        out = {}
        for k, v in layer.items():
            if k in ("k", "v", "k_scale", "v_scale"):  # [kv, N, P, hd|1]
                hspec = tp if _div(v.shape[0], mesh, tp) else None
                out[k] = P(hspec, None, None, None)
            elif k in ("ckv", "kpe"):      # [N, P, rank] — latent, no heads
                out[k] = P(None, None, None)
            elif k == "conv":              # [max_slots, K-1, C]
                out[k] = P(None, None, None)
            elif k == "ssm":               # [max_slots, nh, hd, ds]
                out[k] = P(None, None, None, None)
        return out

    return {"pos": P(None),
            "block_tables": P(None, None),
            "layers": [layer_spec(l) for l in cache_shapes["layers"]]}


def input_sharding(cfg: ModelConfig, mesh: Mesh, par: ParallelismConfig,
                   batch: int):
    dp = par.dp_axes if _div(batch, mesh, par.dp_axes) else None
    return dp


def make_ctx(mesh: Mesh, par: ParallelismConfig) -> ShardingCtx:
    return ShardingCtx(mesh=mesh, dp_axes=par.dp_axes, tp_axis=par.tp_axis,
                       expert_parallel=par.expert_parallel,
                       attn_sharding=par.attn_sharding,
                       fsdp_axes=par.dp_axes if par.fsdp else ())


def named(mesh: Mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))
