"""Topology-aware hierarchical collectives (the rail-optimized insight).

``hierarchical_psum`` implements the paper-faithful 3-phase all-reduce for
gradient synchronization across the 2-pod production mesh:

  1. reduce-scatter over the fat in-pod axis ("data", ICI),
  2. all-reduce of the 1/N shard over the thin cross-pod axis ("pod", DCN),
  3. all-gather back over "data".

Cross-pod traffic shrinks by the in-pod DP size (16× on the production
mesh) versus a flat all-reduce ring spanning both pods — the JAX rendering
of keeping traffic on the rails and off the spine.

``compressed_psum`` adds int8 gradient compression with error feedback on
the cross-pod hop only (DESIGN.md §8): the scarce link carries 1/4 the
bytes while in-pod reduction stays full precision.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _flatten_pad(x, n):
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat, pad


def hierarchical_psum_local(x, *, in_axis: str = "data", cross_axis: str = "pod"):
    """Inside shard_map: hierarchical all-reduce of a local array.

    Equivalent to psum over (in_axis, cross_axis) but with the rail-optimized
    schedule: cross-axis hop moves only 1/|in_axis| of the bytes.
    """
    n = jax.lax.axis_size(in_axis)
    flat, pad = _flatten_pad(x, n)
    shard = flat.reshape(n, -1)
    # Phase 1: reduce-scatter in-pod.
    mine = jax.lax.psum_scatter(shard, in_axis, scatter_dimension=0, tiled=False)
    # Phase 2: all-reduce the shard across pods (thin layer).
    mine = jax.lax.psum(mine, cross_axis)
    # Phase 3: all-gather in-pod.
    full = jax.lax.all_gather(mine, in_axis, axis=0, tiled=False)
    flat = full.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(x.shape)


def int8_compress(x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-tensor int8 quantization. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x)) + 1e-12
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_cross_pod_psum_local(x, error_shard, *, in_axis: str = "data",
                                    cross_axis: str = "pod"):
    """Hierarchical all-reduce with int8 error-feedback compression on the
    cross-pod hop only (the in-pod phases stay full precision).

    ``error_shard``: (ceil(x.size/n),) float32 — this device's quantization
    residual from the previous step (error feedback keeps compressed SGD
    convergent).  Returns (result, new_error_shard).  The thin cross-pod
    link carries int8 payloads + one fp32 scale per pod: 4× fewer bytes.
    """
    n = jax.lax.axis_size(in_axis)
    flat, pad = _flatten_pad(x, n)
    shard = flat.reshape(n, -1)
    mine = jax.lax.psum_scatter(shard, in_axis, scatter_dimension=0,
                                tiled=False).astype(jnp.float32)
    mine = mine + error_shard
    q, scale = int8_compress(mine)
    new_error = mine - q.astype(jnp.float32) * scale
    # Exchange int8 payloads + scales across pods, dequantize-sum locally.
    qs = jax.lax.all_gather(q, cross_axis, axis=0, tiled=False)        # (P, M) int8
    scales = jax.lax.all_gather(scale, cross_axis, axis=0, tiled=False)  # (P,)
    mine_red = jnp.sum(qs.astype(jnp.float32) * scales[:, None], axis=0)
    full = jax.lax.all_gather(mine_red.astype(x.dtype), in_axis, axis=0,
                              tiled=False)
    flat_out = full.reshape(-1)
    if pad:
        flat_out = flat_out[:-pad]
    return flat_out.reshape(x.shape), new_error


def hierarchical_psum(x, mesh: Mesh, *, in_axis: str = "data",
                      cross_axis: str = "pod"):
    """jit-level wrapper: hierarchical all-reduce of a replicated-output
    gradient tree leaf laid out with batch sharding on (cross, in)."""
    if cross_axis not in mesh.axis_names:
        # single-pod mesh: plain psum over the in-pod axis
        fn = jax.shard_map(
            lambda v: jax.lax.psum(v, in_axis), mesh=mesh,
            in_specs=P(*(None,) * x.ndim), out_specs=P(*(None,) * x.ndim),
            check_vma=False)
        return fn(x)
    fn = jax.shard_map(
        partial(hierarchical_psum_local, in_axis=in_axis, cross_axis=cross_axis),
        mesh=mesh, in_specs=P(*(None,) * x.ndim),
        out_specs=P(*(None,) * x.ndim), check_vma=False)
    return fn(x)
