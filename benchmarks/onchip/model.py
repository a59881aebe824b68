"""A configuration file read into the shapes the benchmark works with.

``configs/<name>.json`` holds the published config.json keys as they are
run, the ``program`` block (what the keys do not say: norm, MLP, qk-norm,
dtype) and the ``serving`` block (slots, pages, pool).  :class:`Spec` is
the one view of it that the weights, the reference, the work functions
and the program's ``ModelConfig`` are all built from.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class Spec:
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # rmsnorm | layernorm
    norm_eps: float
    mlp: str             # swiglu | gelu (tanh approximation)
    qk_norm: bool
    rope_theta: float
    tied: bool
    dtype: str
    max_slots: int
    block_size: int
    max_seq_len: int
    num_blocks: int

    @property
    def mlp_mats(self) -> int:
        return 3 if self.mlp == "swiglu" else 2

    def layer_matmul_params(self) -> int:
        """Weights one token multiplies through in one layer."""
        d, hd = self.d_model, self.head_dim
        attn = d * (self.heads + 2 * self.kv_heads) * hd + self.heads * hd * d
        return attn + self.mlp_mats * d * self.d_ff

    def weight_bytes(self, itemsize: int = 2) -> int:
        """Bytes of every weight matrix (norm vectors are negligible and
        left out)."""
        n = self.layers * self.layer_matmul_params() + self.vocab * self.d_model
        if not self.tied:
            n += self.vocab * self.d_model
        return n * itemsize

    def kv_bytes_per_token(self, itemsize: int = 2) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * itemsize


def load_spec(path: Path) -> Spec:
    with open(path) as f:
        c = json.load(f)
    prog, serve = c["program"], c["serving"]
    heads = c["num_attention_heads"]
    return Spec(
        name=c["name"], layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], heads=heads,
        kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or c["hidden_size"] // heads,
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        norm=prog["norm_type"],
        norm_eps=float(c.get("rms_norm_eps", c.get("norm_epsilon", 1e-5))),
        mlp=prog["mlp_type"], qk_norm=bool(prog["qk_norm"]),
        rope_theta=float(c["rope_theta"]),
        tied=bool(c["tie_word_embeddings"]), dtype=prog["dtype"],
        max_slots=serve["max_slots"], block_size=serve["block_size"],
        max_seq_len=serve["max_seq_len"], num_blocks=serve["num_blocks"])


def program_config(spec: Spec):
    """The system under test's ``ModelConfig`` for this spec."""
    from repro.models.config import ModelConfig
    return ModelConfig(
        name=spec.name, family="dense", num_layers=spec.layers,
        d_model=spec.d_model, num_heads=spec.heads,
        num_kv_heads=spec.kv_heads, d_ff=spec.d_ff, vocab_size=spec.vocab,
        head_dim=spec.head_dim, rope_theta=spec.rope_theta,
        qk_norm=spec.qk_norm, mlp_type=spec.mlp, norm_type=spec.norm,
        norm_eps=spec.norm_eps, tie_embeddings=spec.tied, dtype=spec.dtype,
        max_seq_len=spec.max_seq_len)
