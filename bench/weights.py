"""Seeded weights for a dense decoder, made on the device in one jitted call.

The benchmark, not the program, makes the weights: the same arrays feed
the system under test and the plain reference.  They are random (no
checkpoint is loaded), drawn from the run's seed, and stored in the
configuration's serving dtype.

The pytree is laid out as the program takes it (``repro.models.model``
``param_specs`` for a pure-attention decoder with one layer kind):

    embed (V, D)                    tied input embedding and output head
    final_norm (D,)
    blocks.pos0.ln1 / ln2 (L, D)    RMSNorm offsets, applied as (1 + w)
    blocks.pos0.mixer.wq (L, D, H, hd), wk / wv (L, D, KVH, hd),
                       wo (L, H, hd, D), q_norm / k_norm (L, hd)
    blocks.pos0.mlp.wg / wu (L, D, F), wd (L, F, D)

Scales: projections N(0, 1/fan_in), the embedding N(0, 1/D) so the tied
head gives logits of unit spread, norm offsets N(0, 0.1^2).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NORM_STD = 0.1


def shapes(model: dict) -> dict:
    """{path: (shape, std)} for every leaf, in the program's layout."""
    L, D = model["num_layers"], model["d_model"]
    H, KVH = model["num_heads"], model["num_kv_heads"]
    hd, F, V = model["head_dim"], model["d_ff"], model["vocab_size"]
    if not model.get("tie_embeddings", False):
        raise ValueError("only tied embeddings are laid out here")
    leaves = {
        "embed": ((V, D), 1.0 / math.sqrt(D)),
        "final_norm": ((D,), NORM_STD),
        "blocks/pos0/ln1": ((L, D), NORM_STD),
        "blocks/pos0/ln2": ((L, D), NORM_STD),
        "blocks/pos0/mixer/wq": ((L, D, H, hd), 1.0 / math.sqrt(D)),
        "blocks/pos0/mixer/wk": ((L, D, KVH, hd), 1.0 / math.sqrt(D)),
        "blocks/pos0/mixer/wv": ((L, D, KVH, hd), 1.0 / math.sqrt(D)),
        "blocks/pos0/mixer/wo": ((L, H, hd, D), 1.0 / math.sqrt(H * hd)),
        "blocks/pos0/mlp/wg": ((L, D, F), 1.0 / math.sqrt(D)),
        "blocks/pos0/mlp/wu": ((L, D, F), 1.0 / math.sqrt(D)),
        "blocks/pos0/mlp/wd": ((L, F, D), 1.0 / math.sqrt(F)),
    }
    if model.get("qk_norm", False):
        leaves["blocks/pos0/mixer/q_norm"] = ((L, hd), NORM_STD)
        leaves["blocks/pos0/mixer/k_norm"] = ((L, hd), NORM_STD)
    return leaves


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


def make(model: dict, seed: int, dtype) -> dict:
    """The weights pytree for ``model`` from ``seed``, on the default
    device, in ``dtype``: one jitted call."""
    leaves = shapes(model)
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
