"""The plain reference, and the comparison that decides ``correct``.

A float32 ``jax.numpy`` forward pass of the dense decoder the
configuration describes (published equations: pre-norm blocks, RoPE
with the half-split rotation, optional RMSNorm of each head's q and k,
grouped-query causal attention, SwiGLU or tanh-GELU MLP, tied or untied
output head), at ``highest`` matmul precision.  It imports nothing of
the program and reads only the weights the benchmark made.  It runs
layer by layer over one sequence at a time, after the program's state is
freed, so it fits beside the weights.

For each sampled request it runs once over the prompt and the served
tokens, and reads, at every position that produced a served token, how
far that token's logit lies below the reference's best.  Greedy decoding
serves the program's own argmax, so the gap is bounded by how far the
program's arithmetic is from float32; the widest gap is compared.

The control (``precision="fp8"``) is the same pass with every weight
matmul's operands rounded to float8_e4m3fn (per-output-channel scales
for weights, per-token scales for activations): the step below the
configuration's bfloat16 that a later change might take.  Its number is
the gap, under the float32 reference, of the token the fp8 pass puts
first.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .model import Spec

HEAD_ROWS = 256          # logits rows per head call
Q_BLOCK = 512            # query rows per attention block
F8_MAX = 448.0           # largest finite float8_e4m3fn


def _bucket(n: int, lo: int = 64) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _fp8(x, axis):
    """Round ``x`` to float8_e4m3fn with one scale per slice along
    ``axis`` (the reduced axis of the matmul), back in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, precision, w_nk=False):
    """x [T, k] @ w [k, n] (``w_nk``: w given as [n, k]) in float32; under
    ``fp8`` both operands are rounded first (x per row, w per output
    column)."""
    w = w.astype(jnp.float32)
    k_axis = 1 if w_nk else 0
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, k_axis)
    return jax.lax.dot_general(x, w, (((1,), (k_axis,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


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
    sequence-length bucket, reused by every layer and request)."""

    def __init__(self, spec: Spec, params, control: bool = False):
        self.spec, self.params, self.control = spec, params, control
        self._layer = {p: jax.jit(functools.partial(_layer, spec, p))
                       for p in (("f32", "fp8") if control else ("f32",))}
        self._gaps = jax.jit(functools.partial(_gap_rows, spec))

    def _hidden(self, precision, tokens: np.ndarray):
        t = len(tokens)
        padded = np.zeros(_bucket(t), np.int32)
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


def pick_sample(finished: Dict[int, Tuple[np.ndarray, List[int]]],
                seed: int, min_tokens: int, max_requests: int) -> List[int]:
    """Requests to check: the longest finished one (prompt + output),
    then others in an order drawn from ``seed`` until ``min_tokens``
    served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    ids = sorted(finished)
    longest = max(ids, key=lambda i: (len(finished[i][0])
                                      + len(finished[i][1]), -i))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 2]))
    rest = [i for i in rng.permutation(ids) if i != longest]
    pick, tokens = [longest], len(finished[longest][1])
    for i in rest:
        if tokens >= min_tokens or len(pick) >= max_requests:
            break
        pick.append(int(i))
        tokens += len(finished[i][1])
    return pick
