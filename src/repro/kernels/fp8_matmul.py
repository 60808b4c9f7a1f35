"""Pallas TPU kernel: blocked FP8(e4m3) GEMM with fp32 accumulation.

The HPL-MxP hot spot (paper Table 9: "sloppy FP8" trailing-update GEMMs)
adapted to the TPU memory hierarchy: operands live in HBM as e4m3 (half the
bf16 footprint => half the HBM traffic), tiles are staged through VMEM with
MXU-aligned (128-multiple) BlockSpecs, and accumulation happens in an fp32
VMEM scratch tile across the K grid dimension.

Per-tile scales (a_scale: (M/bm,), b_scale: (N/bn,)) keep e4m3's narrow
dynamic range usable — the TPU rendering of tensor-core FP8 scaling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fp8_matmul_kernel(a_ref, b_ref, sa_ref, sb_ref, o_ref, acc_ref, *,
                       k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)          # (bm, bk) e4m3 -> f32
    b = b_ref[...].astype(jnp.float32)          # (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        scale = sa_ref[0] * sb_ref[0]
        o_ref[...] = acc_ref[...] * scale


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def fp8_matmul_pallas(a_q, b_q, a_scale, b_scale, *, bm: int = 128,
                      bn: int = 128, bk: int = 128, interpret: bool = False):
    """a_q: (M, K) e4m3; b_q: (K, N) e4m3; per-row-block / per-col-block
    scales a_scale: (M//bm,), b_scale: (N//bn,). Returns (M, N) f32.

    Every dimension must be an exact multiple of its block size — the
    grid is built by floor division, so a ragged edge would silently
    drop the remainder rows/cols.  Ragged shapes raise ``ValueError``
    naming the offender; the ``repro.kernels.ops.fp8_matmul`` wrapper
    pads to block multiples before calling this."""
    m, k = a_q.shape
    k2, n = b_q.shape
    if k != k2:
        raise ValueError(
            f"fp8_matmul_pallas: contraction mismatch — a_q is (M={m}, "
            f"K={k}) but b_q is (K={k2}, N={n})")
    for dim_name, dim, blk_name, blk in (
            ("M", m, "bm", bm), ("N", n, "bn", bn), ("K", k, "bk", bk)):
        if dim % blk != 0:
            raise ValueError(
                f"fp8_matmul_pallas: {dim_name}={dim} is not a multiple "
                f"of {blk_name}={blk} (shapes a_q={a_q.shape}, "
                f"b_q={b_q.shape}); the grid would silently truncate — "
                "pad to block multiples or use repro.kernels.ops."
                "fp8_matmul, which pads for you")
    k_steps = k // bk

    return pl.pallas_call(
        functools.partial(_fp8_matmul_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
            pl.BlockSpec((1,), lambda i, j, s: (i,)),
            pl.BlockSpec((1,), lambda i, j, s: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_q, b_q, a_scale, b_scale)
