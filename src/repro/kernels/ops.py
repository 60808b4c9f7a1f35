"""jit'd public wrappers for the Pallas kernels.

Handle quantization, padding to block multiples, GQA head expansion, and
the interpret-mode fallback (CPU containers validate kernel bodies with
``interpret=True``; on TPU the same call sites compile to Mosaic).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.mixed_precision import quantize_fp8, F8_MAX
from repro.kernels.fp8_matmul import fp8_matmul_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def fp8_matmul(a, b, *, bm: int = 128, bn: int = 128, bk: int = 128,
               interpret: bool | None = None):
    """f32/bf16 (M,K) @ (K,N) through the FP8 Pallas kernel with per-block
    scaling. Pads every dim to the block multiple."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    a, pm = _pad_to(a, bm, 0)
    a, pk = _pad_to(a, bk, 1)
    b, _ = _pad_to(b, bk, 0)
    b, pn = _pad_to(b, bn, 1)
    m, k = a.shape
    n = b.shape[1]
    # per-row-block / per-col-block scales
    am = jnp.max(jnp.abs(a.reshape(m // bm, bm, k)), axis=(1, 2))
    bm_ = jnp.max(jnp.abs(b.reshape(k, n // bn, bn)), axis=(0, 2))
    sa = jnp.maximum(am, 1e-12) / F8_MAX
    sb = jnp.maximum(bm_, 1e-12) / F8_MAX
    a_q = (a / jnp.repeat(sa, bm)[:, None]).astype(jnp.float8_e4m3fn)
    b_q = (b / jnp.repeat(sb, bn)[None, :]).astype(jnp.float8_e4m3fn)
    out = fp8_matmul_pallas(a_q, b_q, sa.astype(jnp.float32),
                            sb.astype(jnp.float32), bm=bm, bn=bn, bk=bk,
                            interpret=interpret)
    if pm or pn:
        out = out[:out.shape[0] - pm or None, :out.shape[1] - pn or None]
    return out


def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None):
    """q: (B, Sq, H, d); k/v: (B, Skv, KVH, d) — GQA expanded here.

    Returns (B, Sq, H, d)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    B, Sq, H, d = q.shape
    KVH = k.shape[2]
    rep = H // KVH
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, -1, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, -1, d)
    skv = kf.shape[1]
    qf, pq = _pad_to(qf, bq, 1)
    kf, _ = _pad_to(kf, bk, 1)
    vf, _ = _pad_to(vf, bk, 1)
    out = flash_attention_pallas(qf, kf, vf, causal=causal, window=window,
                                 bq=bq, bk=bk, interpret=interpret,
                                 kv_len=skv)
    if pq:
        out = out[:, :Sq]
    return out.reshape(B, H, Sq, d).transpose(0, 2, 1, 3)


def rmsnorm(x, w, *, eps: float = 1e-6, bm: int = 256,
            interpret: bool | None = None):
    """x: (..., D) fused RMSNorm."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    x2, pm = _pad_to(x2, bm, 0)
    out = rmsnorm_pallas(x2, w, eps=eps, bm=bm, interpret=interpret)
    if pm:
        out = out[:out.shape[0] - pm]
    return out.reshape(*lead, x.shape[-1])


def decode_attention(q, k, v, lengths, *, bk: int = 256,
                     interpret: bool | None = None):
    """Single-token decode attention against a KV cache.

    q: (B, 1, H, d); k/v: (B, T, KVH, d); lengths: (B,) valid-key counts.
    Returns (B, 1, H, d)."""
    from repro.kernels.decode_attention import decode_attention_pallas
    interpret = (not _on_tpu()) if interpret is None else interpret
    B, _, H, d = q.shape
    KVH = k.shape[2]
    rep = H // KVH
    kf = jnp.repeat(k, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, -1, d)
    vf = jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, -1, d)
    qf = q[:, 0].transpose(0, 1, 2).reshape(B * H, d)
    kf, _ = _pad_to(kf, bk, 1)
    vf, _ = _pad_to(vf, bk, 1)
    lens = jnp.repeat(lengths, H)
    out = decode_attention_pallas(qf, kf, vf, lens.astype(jnp.int32),
                                  bk=bk, interpret=interpret)
    return out.reshape(B, H, d)[:, None].transpose(0, 1, 2, 3).reshape(B, 1, H, d)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                           k_scale=None, v_scale=None,
                           interpret: bool | None = None):
    """Single-token decode attention over a paged KV pool.

    q: (B, 1, H, d); k_pages/v_pages: (P, page, KVH, d) shared pool;
    page_table: (B, n) int32 per-request logical->physical page map;
    lengths: (B,) valid-key counts.  Returns (B, 1, H, d).

    When the pool is quantized (fp8/int8), pass ``k_scale``/``v_scale``
    shaped (P, page, KVH) — one f32 scale per stored d-vector — and the
    quantized kernel applies them in VMEM (both scales must be given
    together).

    The kernel runs one program per (request, KV head) over all ``rep``
    query heads of the group, so q is viewed as (B, KVH, rep, d) and the
    pool as (KVH, P, page, d) — the big KV arrays are never repeated.
    """
    from repro.kernels.decode_attention import (
        paged_decode_attention_pallas, quantized_paged_decode_attention_pallas)
    if (k_scale is None) != (v_scale is None):
        raise ValueError("paged_decode_attention: pass k_scale and v_scale "
                         "together (quantized pool) or neither")
    interpret = (not _on_tpu()) if interpret is None else interpret
    B, _, H, d = q.shape
    KVH = k_pages.shape[2]
    # the kernel's layout: one (page, d) tile per (kv head, page); named
    # so a profile can tell these copies from the rest
    with jax.named_scope("kv_relayout"):
        kf = k_pages.transpose(2, 0, 1, 3)
        vf = v_pages.transpose(2, 0, 1, 3)
        if k_scale is not None:
            ksf = k_scale.transpose(2, 0, 1)
            vsf = v_scale.transpose(2, 0, 1)
    qf = q.reshape(B, KVH, H // KVH, d)
    if k_scale is not None:
        out = quantized_paged_decode_attention_pallas(
            qf, kf, vf, ksf, vsf, page_table, lengths, interpret=interpret)
    else:
        out = paged_decode_attention_pallas(qf, kf, vf, page_table, lengths,
                                            interpret=interpret)
    return out.reshape(B, 1, H, d)


def paged_decode_attention_latent(q, pool, layer, page_table, lengths, *,
                                  v_lanes: int, scale: float,
                                  interpret: bool | None = None):
    """Single-token decode over a latent paged pool of a layer stack.

    q: (B, 1, H, lanes) latent queries; pool: (G, P, page, lanes), one
    row a token for each of G layers; ``layer``: which of them (may be
    traced); page_table: (B, n) int32; lengths: (B,) valid-key counts.
    Returns (B, 1, H, v_lanes).  The pool is read where it lies: its
    (G*P, page, lanes) view is free, and the layer's pages are the table
    shifted by ``layer * P``."""
    from repro.kernels.decode_attention import (
        paged_decode_attention_latent_pallas)
    interpret = (not _on_tpu()) if interpret is None else interpret
    G, P, page, lanes = pool.shape
    B, _, H, _ = q.shape
    out = paged_decode_attention_latent_pallas(
        q[:, 0], pool.reshape(G * P, page, lanes),
        page_table + layer * P, lengths, v_lanes=v_lanes, scale=scale,
        interpret=interpret)
    return out[:, None]
