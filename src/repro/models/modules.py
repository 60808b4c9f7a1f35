"""Parameter spec system + common neural-net modules (pure JAX).

Parameters are described by ``ParamSpec(shape, axes, init)`` where ``axes``
is a tuple of *logical* axis names consumed by ``repro.parallel.sharding``.
A model is a nested dict of ParamSpecs; ``init_params`` materializes arrays
and ``abstract_params`` produces ShapeDtypeStructs for allocation-free
lowering (the multi-pod dry-run).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"       # normal | zeros | ones
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def init_params(specs, key, dtype=jnp.float32):
    """Materialize a params pytree from a spec tree (smoke tests / examples)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    out = []
    for spec, k in zip(leaves, keys):
        if spec.init == "zeros":
            out.append(jnp.zeros(spec.shape, dtype))
        elif spec.init == "ones":
            out.append(jnp.ones(spec.shape, dtype))
        else:
            fan_in = spec.shape[0] if spec.shape else 1
            std = spec.scale / math.sqrt(max(fan_in, 1))
            out.append((jax.random.normal(k, spec.shape) * std).astype(dtype))
    return jax.tree.unflatten(treedef, out)


def abstract_params(specs, dtype=jnp.float32):
    """ShapeDtypeStruct pytree — no allocation; feeds .lower() in the dry-run."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), specs, is_leaf=is_spec)


def axes_tree(specs):
    """Logical-axes pytree parallel to the params pytree."""
    return jax.tree.map(lambda s: s.axes, specs, is_leaf=is_spec)


def count_params(specs) -> int:
    return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(specs, is_leaf=is_spec))


# ---------------------------------------------------------------------------
# Modules
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor (DeepSeek's ``yarn_get_mscale``)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_frequencies(dim: int, theta: float, scaling=None):
    """(inverse frequencies (dim/2,) float32, factor on cos and sin) of a
    rope over ``dim`` lanes.  ``scaling`` is None (plain RoPE) or a yarn
    group as a config publishes it (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``mscale``, ``mscale_all_dim``): the low frequencies are divided by
    ``factor``, the high ones kept, with a linear ramp between the
    dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
    original context (transformers' ``_compute_yarn_parameters``)."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if scaling is None:
        return (1.0 / pos).astype(np.float32), 1.0
    s = dict(scaling)
    if s.get("type", s.get("rope_type")) != "yarn":
        raise NotImplementedError(f"rope scaling {s!r}")
    factor = float(s["factor"])
    orig = float(s["original_max_position_embeddings"])
    fast, slow = float(s.get("beta_fast", 32)), float(s.get("beta_slow", 1))

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(fast)), 0)
    high = min(math.ceil(corr(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                      # share of the unscaled frequency
    inv = (1.0 / (factor * pos)) * (1.0 - keep) + (1.0 / pos) * keep
    m, m_all = s.get("mscale"), s.get("mscale_all_dim")
    attn = (yarn_mscale(factor, m) / yarn_mscale(factor, m_all)
            if m and m_all else yarn_mscale(factor))
    return inv.astype(np.float32), float(attn)


def rope(x, positions, theta=10_000.0, scaling=None):
    """Rotary embedding. x: (..., seq, heads..., head_dim); positions (..., seq).
    Rotate-half layout: lane i pairs with lane i + head_dim/2.
    ``scaling``: see ``rope_frequencies``."""
    hd = x.shape[-1]
    if scaling is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        attn = 1.0
    else:
        freqs, attn = rope_frequencies(hd, theta, scaling)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., seq, hd/2)
    # insert singleton axes for head dims between seq and head_dim
    extra = x.ndim - angles.ndim
    for _ in range(extra):
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if attn != 1.0:
        cos, sin = cos * attn, sin * attn
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x, wg, wu, wd, compute_dtype):
    g = jnp.einsum("...d,df->...f", x, wg.astype(compute_dtype))
    u = jnp.einsum("...d,df->...f", x, wu.astype(compute_dtype))
    h = jax.nn.silu(g) * u
    return jnp.einsum("...f,fd->...d", h, wd.astype(compute_dtype))


def mlp_specs(d_model: int, d_ff: int, prefix_axes=("embed", "ff")) -> dict:
    e, f = prefix_axes
    return {
        "wg": ParamSpec((d_model, d_ff), (e, f)),
        "wu": ParamSpec((d_model, d_ff), (e, f)),
        "wd": ParamSpec((d_ff, d_model), (f, e)),
    }


def softmax_xent_chunked(x, w_out, labels, *, chunk: int = 512,
                         compute_dtype=jnp.bfloat16):
    """Cross-entropy without materializing full (B,S,V) logits.

    x: (B, S, D) final hidden; w_out: (D, V); labels: (B, S) int32.
    Scans over sequence chunks so peak logits memory is (B, chunk, V).
    Returns (sum_loss, sum_tokens).
    """
    B, S, D = x.shape
    n = max(S // chunk, 1)
    cs = S // n
    xs = x.reshape(B, n, cs, D).swapaxes(0, 1)          # (n, B, cs, D)
    ls = labels.reshape(B, n, cs).swapaxes(0, 1)        # (n, B, cs)

    def body(carry, xl):
        xc, lc = xl
        logits = jnp.einsum("bsd,dv->bsv", xc, w_out.astype(compute_dtype))
        logits = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        loss = jnp.sum((lse - gold) * mask)
        return (carry[0] + loss, carry[1] + jnp.sum(mask)), None

    (total, count), _ = jax.lax.scan(body, (0.0, 0.0), (xs, ls))
    return total, count
