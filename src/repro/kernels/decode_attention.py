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
# Paged decode attention (page gather by DMA, one program per seat and KV head)
# ---------------------------------------------------------------------------
#
# The serving engine stores KV in fixed-size pages drawn from a shared pool;
# a request's cache is the (non-contiguous) set of pages named by its page
# table.  The grid is (seat, KV head, block): one program per seat and KV
# head carries all ``rep`` query heads of that group, so a page is read once
# per group, and each step covers a block of ``ppcb`` pages.  The pools stay
# in HBM; the step copies the block's live pages, named by the
# scalar-prefetched table, into one slot of a two-slot VMEM buffer, one DMA
# per page.  Before it computes, a step starts the copies of the seat's next
# live block (the next block of this head, else the first block of the next
# head) into the other slot, so the gather of one block overlaps the
# attention of the one before.  Blocks past the seat's length start no copy
# and compute nothing; within a live block, pages past the length are not
# copied and their keys are masked.
#
# Mosaic copies an HBM page only as whole 128-lane rows.  A head narrower
# than 128 lanes (d = 64) is therefore packed: each row of a page holds
# ``g = 128 // d`` consecutive tokens, and the kernel runs ``g`` row groups
# of the ``rep`` queries, group ``j`` holding its queries in lanes
# ``j*d:(j+1)*d`` (zeros elsewhere), so it scores token ``g*c + j`` of row
# ``c``.  Each group keeps its own softmax statistics over its tokens, and
# the last step merges the groups exactly as split-K flash decoding does.
# Heads that no packing fits are zero-padded to whole 128-lane rows.
#
# Quantized pools (fp8/int8 values, one f32 scale per stored d-vector) take
# the same path with one more DMA per page and pool for the page's scale row;
# the scales are applied to the f32 scores (K) and probabilities (V), which
# equals dequantizing the tiles first.
#
# A latent pool (multi-head latent attention) is one pool: each row is a
# token's [latent ; rope key], the key of every query head, and its first
# ``v_lanes`` lanes are the value.  One DMA a page, one KV head, all query
# heads in one program: the latent is read once per seat and block.

_BLOCK_TOKENS = 256      # tokens one grid step covers at most
_LANES = 128


def _pages_per_block(page: int, n: int) -> int:
    """Pages one grid step gathers: the largest power of two whose pages
    hold at most ``_BLOCK_TOKENS`` tokens, and no more than the table width
    ``n`` rounded up to a power of two."""
    ppcb = 1
    while 2 * ppcb * page <= _BLOCK_TOKENS and ppcb < n:
        ppcb *= 2
    return ppcb


def _packing(d: int, page: int) -> tuple[int, int]:
    """(tokens per 128-lane row, padded head width) for heads of ``d``."""
    if d < _LANES and _LANES % d == 0 and page % (_LANES // d) == 0:
        return _LANES // d, d
    return 1, -(-d // _LANES) * _LANES


def _pad_last(a, width: int):
    pad = width - a.shape[-1]
    return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)]) if pad else a


def _scale_rows(x, page: int, g: int, rows: int):
    """A block's per-token scales, (ppcb, 1, lanes) with page ``j``'s in
    the first ``page`` lanes of row ``j``, as the (rows, ppcb*page/g)
    matrix the scores take: row group ``r // (rows/g)``, column ``c`` holds
    token ``g*c + group``'s scale.  Built from ops Mosaic lowers (it
    refuses the reshape): a 0/1 matrix on the MXU (exact at HIGHEST)
    spreads each page's scales over the columns, and a select keeps, in
    each column, the page that column's token lies in."""
    ppcb, _, lanes = x.shape
    x = x.reshape(ppcb, lanes)
    w = ppcb * page // g
    iota = jax.lax.broadcasted_iota
    out = jnp.zeros((rows, w), jnp.float32)
    for j in range(g):
        tok = g * iota(jnp.int32, (lanes, w), 1) + j
        spread = (tok % page == iota(jnp.int32, (lanes, w), 0)
                  ).astype(jnp.float32)
        wide = jax.lax.dot_general(x, spread, (((1,), (0,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
        own = ((g * iota(jnp.int32, (ppcb, w), 1) + j) // page
               == iota(jnp.int32, (ppcb, w), 0))
        row = jnp.sum(jnp.where(own, wide, 0.0), axis=0, keepdims=True)
        out = jnp.where(iota(jnp.int32, (rows, w), 0) // (rows // g) == j,
                        row, out)
    return out


def _paged_block_kernel(len_ref, pt_ref, q_ref, *refs, scale: float,
                        page: int, ppcb: int, g: int, n: int, n_pages: int,
                        n_pools: int, v_lanes):
    # refs: the pools in HBM, the output, the softmax scratch, one VMEM
    # buffer per pool, the DMA semaphores and the slot; pools are (k, v),
    # (k, v, k_scale, v_scale) or one latent pool whose first ``v_lanes``
    # lanes are the value
    hbm = refs[:n_pools]
    o_ref, m_ref, l_ref, acc_ref = refs[n_pools:n_pools + 4]
    bufs = refs[n_pools + 4:2 * n_pools + 4]
    sems, slot_ref = refs[2 * n_pools + 4:]
    pools = tuple(zip(hbm, bufs))
    quantized = n_pools == 4
    k_buf, v_buf = (bufs[0], bufs[0]) if n_pools == 1 else bufs[:2]
    if quantized:
        ks_buf, vs_buf = bufs[2:]
    b, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_kvh, n_blocks = pl.num_programs(1), pl.num_programs(2)
    bk = ppcb * page
    length = jnp.minimum(len_ref[b], n * page)

    def each_live_page(hh, blk, slot, method):
        # one copy per pool for each page of block ``blk`` of KV head
        # ``hh`` that holds a key below ``length``
        for j in range(ppcb):
            idx = blk * ppcb + j

            @pl.when(idx * page < length)
            def _():
                row = hh * n_pages + pt_ref[b * n + idx]
                for pool, (src, dst) in enumerate(pools):
                    getattr(pltpu.make_async_copy(
                        src.at[row], dst.at[slot, j],
                        sems.at[pool, slot]), method)()

    @pl.when((h == 0) & (i == 0) & (length > 0))
    def _first_block():
        slot_ref[0] = 0
        each_live_page(0, 0, 0, "start")

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * bk < length)
    def _compute():
        slot = slot_ref[0]
        nxt = 1 - slot

        @pl.when((i + 1) * bk < length)
        def _next_block():
            each_live_page(h, i + 1, nxt, "start")

        @pl.when(((i + 1) * bk >= length) & (h + 1 < n_kvh))
        def _next_head():
            each_live_page(h + 1, 0, nxt, "start")

        each_live_page(h, i, slot, "wait")
        slot_ref[0] = nxt

        q = q_ref[...]                                      # (rows, lanes)
        rows, lanes = q.shape
        w = bk // g                                         # packed rows
        k = k_buf[slot].reshape(w, lanes)
        if v_lanes is None:
            v = v_buf[slot].reshape(w, lanes).astype(jnp.float32)
        else:
            v = v_buf[slot, :, :, :v_lanes].reshape(w, v_lanes).astype(
                jnp.float32)
        if quantized or q.dtype != k.dtype:
            q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        # bf16 operands go to the MXU as they are: their products are
        # exact in the f32 accumulator
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if quantized:
            s = s * _scale_rows(ks_buf[slot], page, g, rows)
        iota = jax.lax.broadcasted_iota
        kpos = (i * bk + g * iota(jnp.int32, (rows, w), 1)
                + iota(jnp.int32, (rows, w), 0) // (rows // g))
        live = kpos < length
        s = jnp.where(live, s, NEG_INF)                     # (rows, w)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        # rows past the length were not copied in this step: zero them, and
        # the scales of keys past it, so stale buffer contents cannot reach
        # the sum through 0 * nan
        if quantized:
            p = p * jnp.where(live, _scale_rows(vs_buf[slot], page, g, rows),
                              0.0)
        vpos = i * bk + g * iota(jnp.int32, (w, 1), 0)
        v = jnp.where(vpos < length, v, 0.0)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == n_blocks - 1)
    def _done():
        # merge the row groups' softmaxes: group j's output for query r is
        # row j*rep + r, lanes j*dp:(j+1)*dp
        rep, d = o_ref.shape
        dp = acc_ref.shape[1] // g
        ms = [m_ref[j * rep:(j + 1) * rep] for j in range(g)]
        top = functools.reduce(jnp.maximum, ms)
        num = den = 0.0
        for j, mj in enumerate(ms):
            wj = jnp.exp(mj - top)
            num = num + wj * acc_ref[j * rep:(j + 1) * rep, j * dp:j * dp + d]
            den = den + wj * l_ref[j * rep:(j + 1) * rep]
        o_ref[...] = (num / jnp.maximum(den, 1e-30)).astype(o_ref.dtype)


def _paged_call(q, pools, scales, page_table, lengths, interpret, *,
                scale=None, v_lanes=None, name=None):
    """Shared launcher: ``pools`` is (k, v) of shape (KVH, P, page, d),
    ``scales`` is () or (k_scale, v_scale) of shape (KVH, P, page); or
    ``pools`` is one latent pool and ``v_lanes`` the width of the value
    it holds in its first lanes.  ``scale`` defaults to d^-0.5."""
    B, KVH, rep, d = q.shape
    dv = d if v_lanes is None else v_lanes
    _, P, page, _ = pools[0].shape
    n = page_table.shape[1]
    ppcb = _pages_per_block(page, n)
    pad = (-n) % ppcb
    if pad:
        # padded entries name the scratch page; they lie past every length
        page_table = jnp.pad(page_table, ((0, 0), (0, pad)))
        n += pad
    g, dp = _packing(d, page)
    rows, lanes = g * rep, g * dp
    # each page is addressed as one leading index: pools as
    # (KVH*P, page/g, lanes), scales as (KVH*P, 1, whole 128-lane rows);
    # packing moves data, so it is named with the wrapper's relayout
    with jax.named_scope("kv_relayout"):
        flat = [_pad_last(a, dp).reshape(KVH * P, page // g, lanes)
                for a in pools]
        flat += [_pad_last(a, -(-page // _LANES) * _LANES
                           ).reshape(KVH * P, 1, -1) for a in scales]
    # row group j carries the queries in lanes j*dp:(j+1)*dp
    qp = _pad_last(q, dp)
    qx = (jnp.eye(g, dtype=q.dtype)[:, None, :, None]
          * qp[:, :, None, :, None, :]).reshape(B, KVH, rows, lanes)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    seat_head = lambda r, width: pl.BlockSpec(
        (None, None, r, width), lambda b, h, i, lens, pt: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # lengths, flat page table
        grid=(B, KVH, n // ppcb),
        in_specs=[seat_head(rows, lanes)] + [hbm] * len(flat),
        out_specs=seat_head(rep, dv),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),  # running max, per row
            pltpu.VMEM((rows, 1), jnp.float32),  # running sum
            pltpu.VMEM((rows, v_lanes or lanes), jnp.float32),
            *[pltpu.VMEM((2, ppcb) + a.shape[1:], a.dtype) for a in flat],
            pltpu.SemaphoreType.DMA((len(flat), 2)),
            pltpu.SMEM((1,), jnp.int32),         # slot of the current block
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_block_kernel,
                          scale=d ** -0.5 if scale is None else scale,
                          page=page, ppcb=ppcb, g=g, n=n, n_pages=P,
                          n_pools=len(flat), v_lanes=v_lanes),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, rep, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32).reshape(-1),
      qx, *flat)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, k_pages, v_pages, page_table, lengths, *,
                                  interpret: bool = False):
    """Decode attention over a paged KV pool.

    q: (B, KVH, rep, d) — the ``rep`` query heads of each KV head;
    k_pages/v_pages: (KVH, P, page, d) shared physical pool; page_table:
    (B, n) int32 — physical page of each seat's i-th logical page (entries
    at or past a seat's length are never read); lengths: (B,) valid-key
    counts.  Returns (B, KVH, rep, d) in q.dtype.
    """
    return _paged_call(q, (k_pages, v_pages), (), page_table, lengths,
                       interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantized_paged_decode_attention_pallas(q, k_pages, v_pages, k_scale,
                                            v_scale, page_table, lengths, *,
                                            interpret: bool = False):
    """Decode attention over a quantized paged KV pool.

    As ``paged_decode_attention_pallas``, with k_pages/v_pages fp8/int8
    and k_scale/v_scale: (KVH, P, page) f32 — one scale per stored
    d-vector, laid out page-for-page with the value pools so the same
    table entry drives the value and scale DMAs.  Tolerance vs the f32
    kernel is bounded by the storage format's relative error (e4m3: 3
    mantissa bits, ~6%/element on K/V — see tests/test_kernels.py).
    """
    if k_pages.dtype == jnp.uint8:
        # fp8 pools travel as uint8 bit patterns through the serving
        # stack (core.mixed_precision.kv_storage_dtype); recover the
        # e4m3 view here so the in-kernel f32 cast reads real values
        k_pages = jax.lax.bitcast_convert_type(k_pages, jnp.float8_e4m3fn)
        v_pages = jax.lax.bitcast_convert_type(v_pages, jnp.float8_e4m3fn)
    return _paged_call(q, (k_pages, v_pages), (k_scale, v_scale), page_table,
                       lengths, interpret)


@functools.partial(jax.jit, static_argnames=("v_lanes", "scale", "interpret"))
def paged_decode_attention_latent_pallas(q, pool, page_table, lengths, *,
                                         v_lanes: int, scale: float,
                                         interpret=False):
    """Decode attention over a latent paged pool (multi-head latent
    attention in its absorbed form).

    q: (B, H, lanes) — each head's latent query; pool: (R, page, lanes),
    one row per token: the key of every head, its first ``v_lanes`` lanes
    the value; page_table: (B, n) int32 row indices into ``pool``;
    lengths: (B,) valid-key counts.  Returns (B, H, v_lanes) in q.dtype.
    The kernel is named ``paged_decode_attention_latent`` in a trace."""
    B, H, lanes = q.shape
    out = _paged_call(q[:, None], (pool[None],), (), page_table, lengths,
                      interpret, scale=scale, v_lanes=v_lanes,
                      name="paged_decode_attention_latent")
    return out[:, 0]
