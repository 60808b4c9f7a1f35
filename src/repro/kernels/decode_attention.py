"""Pallas TPU kernel: single-query (decode) flash attention.

The serving hot spot: one new query token attends against a long KV cache
(decode_32k: 32768 keys; long_500k: 524288).  Memory-bound by the KV read —
so the kernel streams K/V tiles HBM->VMEM exactly once, carries online-
softmax statistics in scratch, and masks by each row's current length
``pos`` (slots beyond the write position are dead).

Layout: q (BH, d); k/v (BH, T, d); lengths (BH,) int32 (number of valid
keys = pos+1).  GQA expansion happens in the ops.py wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, bk: int, k_steps: int):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0]
    # skip tiles entirely beyond the valid length
    @pl.when(ki * bk < length)
    def _compute():
        q = q_ref[...].astype(jnp.float32)                  # (1, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(kpos < length, s, NEG_INF)            # (1, bk)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                    # (bk, d)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _done():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention_pallas(q, k, v, lengths, *, bk: int = 256,
                            interpret: bool = False):
    """q: (BH, d); k/v: (BH, T, d); lengths: (BH,) valid-key counts.
    Returns (BH, d) in q.dtype.

    ``bk`` is clamped to the cache length and the cache is zero-padded
    up to the next tile multiple (padded keys sit beyond every row's
    ``lengths`` so the in-kernel mask drops them), so any ``T`` works —
    e.g. the fixed-slot engine's ``max_len + 1`` scratch layouts and
    odd ``max_len`` configs that are not multiples of the tile."""
    bh, d = q.shape
    _, t, _ = k.shape
    bk = min(bk, t)
    pad = (-t) % bk
    if pad:
        widths = [(0, 0), (0, pad), (0, 0)]
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
        t += pad
    k_steps = t // bk
    scale = d ** -0.5

    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, bk=bk, k_steps=k_steps),
        grid=(bh, k_steps),
        in_specs=[
            pl.BlockSpec((1,), lambda b, s: (b,)),
            pl.BlockSpec((1, d), lambda b, s: (b, 0)),
            pl.BlockSpec((1, bk, d), lambda b, s: (b, s, 0)),
            pl.BlockSpec((1, bk, d), lambda b, s: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, d), lambda b, s: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, q, k, v)


# ---------------------------------------------------------------------------
# Paged decode attention (gather-over-page-table)
# ---------------------------------------------------------------------------
#
# The serving engine stores KV in fixed-size pages drawn from a shared pool;
# a request's cache is the (non-contiguous) set of pages named by its page
# table.  The kernel walks the page table with scalar prefetch: the block
# index_map reads ``page_table[b, i]`` so the DMA for grid step (b, i) pulls
# exactly that physical page HBM->VMEM — no contiguous copy of the request's
# KV is ever materialized.
#
# Mosaic takes a block only when its last two dims are multiples of
# (8, 128) or equal the array's.  A one-row block of a (BH, d) query would
# be neither, so the query, the output and the per-slot scales travel
# with a singleton middle axis — (BH, 1, d) and (rows, 1, page) — and
# their blocks are (1, 1, d) and (1, 1, page).


def _paged_decode_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, scale: float, page: int,
                         n_pages: int):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    # pages entirely beyond the valid length are dead (their table entries
    # point at the scratch page) — skip the whole tile
    @pl.when(i * page < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (1, d)
        k = k_ref[0].astype(jnp.float32)                    # (page, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = i * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        s = jnp.where(kpos < length, s, NEG_INF)            # (1, page)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                    # (page, d)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == n_pages - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, k_pages, v_pages, page_table, lengths, *,
                                  interpret: bool = False):
    """Decode attention over a paged KV pool.

    q: (BH, d); k_pages/v_pages: (P, page, d) shared physical pool;
    page_table: (BH, n) int32 — physical page of each row's i-th logical
    page (dead entries must still name a valid page, e.g. scratch page 0);
    lengths: (BH,) valid-key counts.  Returns (BH, d) in q.dtype.
    """
    bh, d = q.shape
    _, page, _ = k_pages.shape
    n_pages = page_table.shape[1]
    scale = d ** -0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # lengths, page_table
        grid=(bh, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda b, i, lens, pt: (b, 0, 0)),
            pl.BlockSpec((1, page, d), lambda b, i, lens, pt: (pt[b, i], 0, 0)),
            pl.BlockSpec((1, page, d), lambda b, i, lens, pt: (pt[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, i, lens, pt: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, page=page,
                          n_pages=n_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32), q[:, None],
      k_pages, v_pages)
    return out[:, 0]


# ---------------------------------------------------------------------------
# Quantized paged decode attention (fp8/int8 pages + per-slot scales)
# ---------------------------------------------------------------------------
#
# Same gather-over-page-table structure, but the pool stores K/V quantized
# (fp8 e4m3 or int8) with one f32 scale per stored d-vector.  The scale
# arrays ride the SAME scalar-prefetched page table as the value pages —
# grid step (b, i) DMAs page ``pt[b, i]``'s values AND its scale row into
# VMEM together — and the tiles are dequantized to f32 in VMEM before the
# flash inner loop, so the softmax/accumulate math is identical to the
# full-precision kernel.


def _quantized_paged_decode_kernel(len_ref, pt_ref, q_ref, k_ref, v_ref,
                                   ks_ref, vs_ref, o_ref, m_ref, l_ref,
                                   acc_ref, *, scale: float, page: int,
                                   n_pages: int):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(i * page < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (1, d)
        # dequantize in VMEM: values (page, d) * per-slot scales (page, 1)
        k = k_ref[0].astype(jnp.float32) * ks_ref[0, 0][:, None]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = i * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        s = jnp.where(kpos < length, s, NEG_INF)            # (1, page)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32) * vs_ref[0, 0][:, None]
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == n_pages - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantized_paged_decode_attention_pallas(q, k_pages, v_pages, k_scale,
                                            v_scale, page_table, lengths, *,
                                            interpret: bool = False):
    """Decode attention over a quantized paged KV pool.

    q: (BH, d); k_pages/v_pages: (P, page, d) fp8/int8 physical pool;
    k_scale/v_scale: (P, page) f32 — one scale per stored d-vector,
    laid out page-for-page with the value pools so the scalar-prefetched
    page table drives both DMAs; page_table: (BH, n) int32; lengths:
    (BH,).  Returns (BH, d) in q.dtype.  Tolerance vs the f32 kernel is
    bounded by the storage format's relative error (e4m3: 3 mantissa
    bits, ~6%/element on K/V — see tests/test_kernels.py).
    """
    if k_pages.dtype == jnp.uint8:
        # fp8 pools travel as uint8 bit patterns through the serving
        # stack (core.mixed_precision.kv_storage_dtype); recover the
        # e4m3 view here so the in-kernel f32 cast reads real values
        k_pages = jax.lax.bitcast_convert_type(k_pages, jnp.float8_e4m3fn)
        v_pages = jax.lax.bitcast_convert_type(v_pages, jnp.float8_e4m3fn)
    bh, d = q.shape
    _, page, _ = k_pages.shape
    n_pages = page_table.shape[1]
    scale = d ** -0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # lengths, page_table
        grid=(bh, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda b, i, lens, pt: (b, 0, 0)),
            pl.BlockSpec((1, page, d), lambda b, i, lens, pt: (pt[b, i], 0, 0)),
            pl.BlockSpec((1, page, d), lambda b, i, lens, pt: (pt[b, i], 0, 0)),
            pl.BlockSpec((1, 1, page), lambda b, i, lens, pt: (pt[b, i], 0, 0)),
            pl.BlockSpec((1, 1, page), lambda b, i, lens, pt: (pt[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda b, i, lens, pt: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_quantized_paged_decode_kernel, scale=scale,
                          page=page, n_pages=n_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32), q[:, None],
      k_pages, v_pages, k_scale[:, None], v_scale[:, None])
    return out[:, 0]
