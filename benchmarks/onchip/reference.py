"""The comparison that decides ``correct``, and what every architecture's
reference shares.

Each architecture module (``archs/<arch>.py``) brings a ``Reference``: a
float32 ``jax.numpy`` forward pass of the published equations of the
model the configuration describes, at ``highest`` matmul precision.  It
imports nothing of the program and reads only the weights the benchmark
made.  It runs after the program's state is freed, in pieces that fit
beside the weights.

For each sampled request it runs once over the prompt and the served
tokens, and reads, at every position that produced a served token, how
far that token's logit lies below the reference's best.  Greedy decoding
serves the program's own argmax, so the gap is bounded by how far the
program's arithmetic is from float32; the widest gap is compared.

The control (``precision="fp8"``) is the same pass with every weight
matmul's operands rounded to float8_e4m3fn (per-output-channel scales
for weights, per-token scales for activations, :func:`mm`): the step
below the configuration's bfloat16 that a later change might take.  Its
number is the gap, under the float32 reference, of the token the fp8
pass puts first.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0           # largest finite float8_e4m3fn


def bucket(n: int, lo: int = 64) -> int:
    """The power-of-two length (from ``lo``) a sequence is padded to, so
    that a reference compiles once per bucket."""
    b = lo
    while b < n:
        b *= 2
    return b


def fp8(x, axis):
    """Round ``x`` to float8_e4m3fn with one scale per slice along
    ``axis`` (the reduced axis of the matmul), back in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, precision, w_nk=False):
    """x [T, k] @ w [k, n] (``w_nk``: w given as [n, k]) in float32; under
    ``fp8`` both operands are rounded first (x per row, w per output
    column)."""
    w = w.astype(jnp.float32)
    k_axis = 1 if w_nk else 0
    if precision == "fp8":
        x, w = fp8(x, -1), fp8(w, k_axis)
    return jax.lax.dot_general(x, w, (((1,), (k_axis,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


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
