"""Random weights from ``--seed``, made on the device in one compiled call.

Each architecture module (``archs/<arch>.py``) gives the tree: the layout
the program reads, each leaf with the distribution the program's own
initialiser gives it.  The benchmark makes the weights itself, so the
reference and the program are handed the same tree and neither made it.
"""
from __future__ import annotations

import functools

import jax
import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from all the bits of ``seed`` (``PRNGKey`` keeps only
    the low 32 of a Python int)."""
    words = np.random.SeedSequence([int(seed), stream]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def on_device(tree, spec, seed: int):
    """``tree(spec, key)``, the whole tree, on the default device from one
    jitted call with the key drawn from ``seed``."""
    fn = jax.jit(functools.partial(tree, spec))
    params = fn(seed_key(seed))
    jax.block_until_ready(params)
    return params
