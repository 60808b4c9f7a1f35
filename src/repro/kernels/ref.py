"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fp8_matmul_ref(a_q, b_q, a_scale, b_scale, *, bm: int = 128, bn: int = 128):
    """Dequantize-then-matmul oracle. Same per-block scale layout as the
    kernel: a_scale[i] applies to rows [i*bm, (i+1)*bm) — so, like the
    kernel, M and N must be exact multiples of the block sizes."""
    m, _ = a_q.shape
    _, n = b_q.shape
    for dim_name, dim, blk_name, blk in (("M", m, "bm", bm),
                                         ("N", n, "bn", bn)):
        if dim % blk != 0:
            raise ValueError(
                f"fp8_matmul_ref: {dim_name}={dim} is not a multiple of "
                f"{blk_name}={blk} (shapes a_q={a_q.shape}, "
                f"b_q={b_q.shape}); the per-block scale layout cannot "
                "cover a ragged edge — pad to block multiples first")
    sa = jnp.repeat(a_scale, bm)[:, None]
    sb = jnp.repeat(b_scale, bn)[None, :]
    out = jax.lax.dot_general(
        a_q.astype(jnp.float32), b_q.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return out * (sa * sb)


def attention_ref(q, k, v, *, causal: bool = True, window: int | None = None):
    """Dense-softmax oracle. q: (BH, Sq, d); k/v: (BH, Skv, d)."""
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    qp = jnp.arange(q.shape[1])[:, None]
    kp = jnp.arange(k.shape[1])[None, :]
    keep = jnp.ones_like(s[0], bool)
    if causal:
        keep &= kp <= qp
    if window is not None:
        keep &= kp > qp - window
    s = jnp.where(keep[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def rmsnorm_ref(x, w, *, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def decode_attention_ref(q, k, v, lengths):
    """Oracle for single-query decode. q: (BH, d); k/v: (BH, T, d)."""
    d = q.shape[-1]
    s = jnp.einsum("bd,btd->bt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    t = jnp.arange(k.shape[1])[None, :]
    s = jnp.where(t < lengths[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bt,btd->bd", p, v.astype(jnp.float32)).astype(q.dtype)


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, lengths):
    """Oracle for paged decode: gather each seat's pages into a contiguous
    cache per KV head, then dense decode for each of its query heads.
    q: (B, KVH, rep, d); k_pages/v_pages: (KVH, P, page, d); page_table:
    (B, n) int32; lengths: (B,).  Returns (B, KVH, rep, d)."""
    B, KVH, rep, d = q.shape

    def gather(pages):          # -> (B*KVH*rep, n*page, d)
        per_head = pages[:, page_table].reshape(KVH, B, -1, d)
        return jnp.repeat(per_head.transpose(1, 0, 2, 3), rep,
                          axis=1).reshape(B * KVH * rep, -1, d)

    out = decode_attention_ref(q.reshape(-1, d), gather(k_pages),
                               gather(v_pages), jnp.repeat(lengths, KVH * rep))
    return out.reshape(q.shape)


def quantized_paged_decode_attention_ref(q, k_pages, v_pages, k_scale,
                                         v_scale, page_table, lengths):
    """Oracle for paged decode over quantized pools: dequantize every
    page with its per-(slot, head) scales, then run the f32 paged
    oracle.  k_pages/v_pages: (KVH, P, page, d) fp8/int8 — uint8 arrays
    are fp8 bit patterns (core.mixed_precision.kv_storage_dtype) and are
    bitcast to e4m3 before the value cast; k_scale/v_scale: (KVH, P,
    page) f32 — one scale per stored d-vector."""
    if k_pages.dtype == jnp.uint8:
        k_pages = jax.lax.bitcast_convert_type(k_pages, jnp.float8_e4m3fn)
        v_pages = jax.lax.bitcast_convert_type(v_pages, jnp.float8_e4m3fn)
    k = k_pages.astype(jnp.float32) * k_scale[..., None]
    v = v_pages.astype(jnp.float32) * v_scale[..., None]
    return paged_decode_attention_ref(q, k, v, page_table, lengths)
