"""The dense decoder: grouped-query attention with RoPE, one MLP per
layer (``program.family`` ``dense``).

One architecture module of the benchmark (``model.py`` gives the
interface).  It holds everything the benchmark knows of this
architecture:

* :class:`Spec` and :func:`load_spec`: the configuration's published
  keys (heads, kv heads, ``intermediate_size``) and its ``program`` and
  ``serving`` blocks (norm, MLP, qk-norm, dtype; slots, pages, pool);
* :func:`program_config`: the system under test's ``ModelConfig``;
* :func:`make_params`: random weights from ``--seed`` in the program's
  layout (``embed``, ``final_norm``, optional ``lm_head``, one block per
  layer), each leaf with the distribution the program's own initialiser
  gives it: matrices normal times fan-in^-0.5 in the served dtype, norm
  scales 1 and biases 0 in float32;
* :class:`Reference`: a float32 ``jax.numpy`` forward pass of the
  published equations (pre-norm blocks, RoPE with the half-split
  rotation, optional RMSNorm of each head's q and k, grouped-query
  causal attention, SwiGLU or tanh-GELU MLP, tied or untied output head)
  at ``highest`` matmul precision, with its fp8 control
  (``reference.py`` says what the comparison reads);
* :func:`decode_step`, :func:`prefill` and :func:`paged_attention`: the
  least work of a step and of one paged-kernel call (``work.py`` gives
  the rule).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from onchip import reference, weights
from onchip.reference import mm as _mm

HEAD_ROWS = 256          # logits rows per head call
Q_BLOCK = 512            # query rows per attention block


# ------------------------------------------------------------ the spec
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


def load_spec(c: dict) -> Spec:
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


# --------------------------------------------------------- the weights
def _norm_params(spec: Spec):
    p = {"scale": jnp.ones((spec.d_model,), jnp.float32)}
    if spec.norm == "layernorm":
        p["bias"] = jnp.zeros((spec.d_model,), jnp.float32)
    return p


def _tree(spec: Spec, key):
    dt = jnp.dtype(spec.dtype)
    d, h, kv, hd, ff = (spec.d_model, spec.heads, spec.kv_heads,
                        spec.head_dim, spec.d_ff)
    keys = iter(jax.random.split(key, 4 + 8 * spec.layers))

    def mat(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * fan_in ** -0.5).astype(dt)

    params = {"embed": mat((spec.vocab, d), d),
              "final_norm": _norm_params(spec), "layers": []}
    if not spec.tied:
        params["lm_head"] = mat((d, spec.vocab), d)
    for _ in range(spec.layers):
        attn = {"wq": mat((d, h * hd), d), "wk": mat((d, kv * hd), d),
                "wv": mat((d, kv * hd), d), "wo": mat((h * hd, d), h * hd)}
        if spec.qk_norm:
            attn["q_norm"] = jnp.ones((hd,), jnp.float32)
            attn["k_norm"] = jnp.ones((hd,), jnp.float32)
        if spec.mlp == "swiglu":
            mlp = {"w_gate": mat((d, ff), d), "w_up": mat((d, ff), d),
                   "w_down": mat((ff, d), ff)}
        else:
            mlp = {"w_up": mat((d, ff), d), "w_down": mat((ff, d), ff)}
        params["layers"].append({"attn_norm": _norm_params(spec),
                                 "mlp_norm": _norm_params(spec),
                                 "attn": attn, "mlp": mlp})
    return params


def make_params(spec: Spec, seed: int):
    return weights.on_device(_tree, spec, seed)


# ------------------------------------------------------- the reference
def _norm(x, p, spec: Spec):
    if spec.norm == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + spec.norm_eps) * p["scale"] \
            + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + spec.norm_eps) \
        * p["scale"]


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [T, heads, hd]; position t rotates pair (i, i + hd/2) by
    t * theta^(-2i/hd)."""
    t, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(spec: Spec, precision: str, lp, x):
    """One decoder block over x [T, d] (float32)."""
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    lp = f32(lp)
    t = x.shape[0]
    h, kv, hd = spec.heads, spec.kv_heads, spec.head_dim
    a = lp["attn"]
    y = _norm(x, lp["attn_norm"], spec)
    q = _mm(y, a["wq"], precision).reshape(t, h, hd)
    k = _mm(y, a["wk"], precision).reshape(t, kv, hd)
    v = _mm(y, a["wv"], precision).reshape(t, kv, hd)
    if spec.qk_norm:
        q = _rms(q, a["q_norm"], spec.norm_eps)
        k = _rms(k, a["k_norm"], spec.norm_eps)
    q, k = _rope(q, spec.rope_theta), _rope(k, spec.rope_theta)
    qg = q.reshape(t, kv, h // kv, hd)
    outs = []
    for q0 in range(0, t, Q_BLOCK):          # query blocks bound memory
        qb = qg[q0:q0 + Q_BLOCK]
        s = jnp.einsum("qgrd,kgd->grqk", qb, k,
                       precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
        causal = (q0 + jnp.arange(qb.shape[0]))[:, None] \
            >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("grqk,kgd->qgrd", p, v,
                               precision=jax.lax.Precision.HIGHEST))
    o = jnp.concatenate(outs).reshape(t, h * hd)
    x = x + _mm(o, a["wo"], precision)
    y = _norm(x, lp["mlp_norm"], spec)
    m = lp["mlp"]
    if spec.mlp == "swiglu":
        z = jax.nn.silu(_mm(y, m["w_gate"], precision)) \
            * _mm(y, m["w_up"], precision)
    else:
        z = jax.nn.gelu(_mm(y, m["w_up"], precision), approximate=True)
    return x + _mm(z, m["w_down"], precision)


def _head_w(params, spec: Spec):
    return params["embed"] if spec.tied else params["lm_head"]


def _logits(spec: Spec, precision: str, final_norm, w, x):
    fn = jax.tree.map(lambda a: a.astype(jnp.float32), final_norm)
    return _mm(_norm(x, fn, spec), w, precision, w_nk=spec.tied)


def _gap_rows(spec, final_norm, w, x_ref, x_ctl, tok):
    """Per row: the f32 reference's best logit minus its logit of
    ``tok`` (the served token) and of the control's first token."""
    ref = _logits(spec, "f32", final_norm, w, x_ref)
    best = ref.max(-1)
    served = best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
    if x_ctl is None:
        return served, None
    ctl_top = _logits(spec, "fp8", final_norm, w, x_ctl).argmax(-1)
    return served, best - jnp.take_along_axis(ref, ctl_top[:, None], -1)[:, 0]


class Reference:
    """Compiled pieces of the reference for one spec (one compile per
    sequence-length bucket, reused by every layer and request).  It runs
    layer by layer over one sequence at a time, so it fits beside the
    weights."""

    def __init__(self, spec: Spec, params, control: bool = False):
        self.spec, self.params, self.control = spec, params, control
        self._layer = {p: jax.jit(functools.partial(_layer, spec, p))
                       for p in (("f32", "fp8") if control else ("f32",))}
        self._gaps = jax.jit(functools.partial(_gap_rows, spec))

    def _hidden(self, precision, tokens: np.ndarray):
        t = len(tokens)
        padded = np.zeros(reference.bucket(t), np.int32)
        padded[:t] = tokens
        x = jnp.take(self.params["embed"], jnp.asarray(padded),
                     axis=0).astype(jnp.float32)
        for lp in self.params["layers"]:
            x = self._layer[precision](lp, x)
        return x

    def gaps(self, prompt: np.ndarray, served: Sequence[int]
             ) -> Tuple[np.ndarray, np.ndarray]:
        """Gaps at every served position: (served token's, control's
        first token's; empty without a control)."""
        served = np.asarray(served, np.int32)
        seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
        p0 = len(prompt) - 1
        x_ref = self._hidden("f32", seq)
        x_ctl = self._hidden("fp8", seq) if self.control else None
        w = _head_w(self.params, self.spec)
        fn = self.params["final_norm"]
        out_s: List[np.ndarray] = []
        out_c: List[np.ndarray] = []
        for r0 in range(0, len(served), HEAD_ROWS):
            rows = np.arange(r0, r0 + HEAD_ROWS)
            pos = np.minimum(p0 + rows, x_ref.shape[0] - 1)
            tok = np.zeros(HEAD_ROWS, np.int32)
            n = min(HEAD_ROWS, len(served) - r0)
            tok[:n] = served[r0:r0 + n]
            s, c = self._gaps(fn, w, x_ref[pos],
                              None if x_ctl is None else x_ctl[pos],
                              jnp.asarray(tok))
            out_s.append(np.asarray(s)[:n])
            if c is not None:
                out_c.append(np.asarray(c)[:n])
        return (np.concatenate(out_s),
                np.concatenate(out_c) if out_c else np.zeros(0))


# ------------------------------------------------------- the least work
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
