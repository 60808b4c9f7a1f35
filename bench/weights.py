"""Seeded weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the same arrays feed
the system under test and the plain reference.  They are random (no
checkpoint is loaded), drawn from the run's seed, and stored in the
configuration's serving dtype.  What to draw, the table of leaves with
their shapes and scales in the program's layout, is the configuration's
reference module's (``shapes(model)`` in ``bench/reference/<r>.py``);
this module only draws it: leaf ``i`` of the table in sorted order is
N(0, std^2) from ``fold_in(key, i)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def key_words(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed (64 bits and more)."""
    return np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(
        2, np.uint32)


def make(leaves: dict, seed: int, dtype) -> dict:
    """The weights pytree for the table ``leaves`` ({path: (shape,
    std)}) from ``seed``, on the default device, in ``dtype``: one
    jitted call."""
    dtype = jnp.dtype(dtype)

    @jax.jit
    def build(words):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        flat = {}
        for i, (path, (shape, std)) in enumerate(sorted(leaves.items())):
            k = jax.random.fold_in(key, i)
            flat[path] = (jax.random.normal(k, shape, jnp.float32)
                          * std).astype(dtype)
        return _nest(flat)

    return build(jnp.asarray(key_words(seed)))
