"""Random weights from ``--seed``, made on the device in one compiled call.

The tree has the layout the program's dense decoder reads (``embed``,
``final_norm``, optional ``lm_head``, one block per layer) and each leaf
the distribution the program's own initialiser gives it: matrices normal
times fan-in^-0.5 in the served dtype, norm scales 1 and biases 0 in
float32.  The benchmark makes them itself, so the reference and the
program are handed the same tree and neither made it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .model import Spec


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from all the bits of ``seed`` (``PRNGKey`` keeps only
    the low 32 of a Python int)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def _norm(spec: Spec):
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

    params = {"embed": mat((spec.vocab, d), d), "final_norm": _norm(spec),
              "layers": []}
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
        params["layers"].append({"attn_norm": _norm(spec),
                                 "mlp_norm": _norm(spec), "attn": attn,
                                 "mlp": mlp})
    return params


def make_params(spec: Spec, seed: int):
    """The whole tree, on the default device, from one jitted call."""
    fn = jax.jit(functools.partial(_tree, spec))
    params = fn(seed_key(seed))
    jax.block_until_ready(params)
    return params
