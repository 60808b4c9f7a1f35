"""Attention: GQA with RoPE, optional qk-norm / sliding window, KV cache.

Prefill/train uses a query-chunked implementation (bounded score memory —
32k×32k scores are never materialized); decode attends a single query token
against the cache.  A Pallas flash-attention kernel (repro.kernels.flash
_attention) can be swapped in via ``impl='pallas'`` for TPU runs; the
chunked jnp path is the portable oracle and the dry-run default.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import mixed_precision as mp
from repro.models.modules import ParamSpec, rms_norm, rope

NEG_INF = -1e30


def attn_specs(cfg, *, cross: bool = False) -> dict:
    d, h, kvh, hd = (cfg.d_model, cfg.padded_heads, cfg.padded_kv_heads,
                     cfg.resolved_head_dim)
    specs = {
        "wq": ParamSpec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, kvh, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, kvh, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm and not cross:
        specs["q_norm"] = ParamSpec((hd,), ("head_dim",), "zeros")
        specs["k_norm"] = ParamSpec((hd,), ("head_dim",), "zeros")
    return specs


def _head_mask(cfg, out):
    """Zero the padded heads (cfg.pad_heads_to): keeps the padded model
    EXACTLY equal to the assigned config while enabling 16-way TP."""
    if cfg.pad_heads_to is None:
        return out
    hp = cfg.padded_heads
    mask = (jnp.arange(hp) < cfg.num_heads).astype(out.dtype)
    return out * mask[None, None, :, None]


def _project_qkv(p, cfg, xq, xkv, positions_q, positions_kv, *, use_rope=True):
    dt = xq.dtype
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"].astype(dt))
    k = jnp.einsum("btd,dnk->btnk", xkv, p["wk"].astype(dt))
    v = jnp.einsum("btd,dnk->btnk", xkv, p["wv"].astype(dt))
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if use_rope:
        q = rope(q, positions_q, cfg.rope_theta)
        k = rope(k, positions_kv, cfg.rope_theta)
    return q, k, v


def _gqa_attend(q, k, v, mask_fn, sq_positions, kv_positions, scale):
    """q: (B,Sq,H,hd); k,v: (B,T,KVH,hd). mask_fn(qpos, kpos)->bool keep."""
    B, Sq, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    qg = q.reshape(B, Sq, KVH, G, hd)
    scores = jnp.einsum("bsngd,btnd->bngst", qg, k) * scale   # (B,KVH,G,Sq,T)
    keep = mask_fn(sq_positions[:, :, None], kv_positions[:, None, :])  # (B,Sq,T)
    scores = jnp.where(keep[:, None, None], scores.astype(jnp.float32), NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bngst,btnd->bsngd", probs, v)
    return out.reshape(B, Sq, H, hd)


def attention(p, cfg, x, *, kind: str = "attn", causal: bool = True,
              positions=None, x_kv=None, kv_positions=None,
              q_chunk: int = 1024, use_rope: bool = True):
    """Full-sequence (train / prefill) attention.

    kind: 'attn' (global) or 'attn_local' (sliding window cfg.sliding_window).
    x_kv: source for K/V in cross-attention (positions via kv_positions).
    Returns (out, (k, v)) — k/v returned so prefill can seed the cache.
    """
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cross = x_kv is not None
    xkv = x_kv if cross else x
    if kv_positions is None:
        kv_positions = (jnp.broadcast_to(jnp.arange(xkv.shape[1], dtype=jnp.int32),
                                         (B, xkv.shape[1])) if cross else positions)
    q, k, v = _project_qkv(p, cfg, x, xkv, positions, kv_positions,
                           use_rope=use_rope and not cross)
    hd = cfg.resolved_head_dim
    scale = hd ** -0.5
    window = cfg.sliding_window if kind == "attn_local" else None

    def mask_fn(qp, kp):
        keep = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
        if causal and not cross:
            keep &= kp <= qp
        if window is not None:
            keep &= kp > qp - window
        return keep

    n_chunks = S // q_chunk if (S % q_chunk == 0 and S > q_chunk) else 1
    if n_chunks <= 1:
        out = _gqa_attend(q, k, v, mask_fn, positions, kv_positions, scale)
    else:
        qs = q.reshape(B, n_chunks, q_chunk, *q.shape[2:]).swapaxes(0, 1)
        ps = positions.reshape(B, n_chunks, q_chunk).swapaxes(0, 1)

        def body(_, qc):
            qi, pi = qc
            return None, _gqa_attend(qi, k, v, mask_fn, pi, kv_positions, scale)

        _, outs = jax.lax.scan(body, None, (qs, ps))
        out = outs.swapaxes(0, 1).reshape(B, S, *outs.shape[3:])
    proj = jnp.einsum("bshd,hdD->bsD", _head_mask(cfg, out),
                      p["wo"].astype(x.dtype))
    return proj, (k, v)


def decode_attention(p, cfg, x, cache_k, cache_v, pos, *, kind: str = "attn",
                     cross: bool = False, use_rope: bool = True):
    """Single-token decode. x: (B,1,D); cache_k/v: (B,T,KVH,hd); pos: (B,) int32.

    Returns (out, new_k, new_v).  For cross-attention the cache holds the
    (fixed) encoder K/V and is not updated.
    """
    B = x.shape[0]
    T = cache_k.shape[1]
    if cross:
        k, v = cache_k, cache_v
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
        kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        keep = jnp.ones((B, 1, T), bool)
    else:
        q, k_new, v_new = _project_qkv(
            p, cfg, x, x, pos[:, None], pos[:, None], use_rope=use_rope)
        # write the new K/V at position pos (per-batch dynamic index)
        upd = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, axis=0))
        k = upd(cache_k, k_new, pos)
        v = upd(cache_v, v_new, pos)
        kv_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        keep = kv_pos[:, None, :] <= pos[:, None, None]
        if kind == "attn_local" and cfg.sliding_window is not None:
            keep &= kv_pos[:, None, :] > (pos[:, None, None] - cfg.sliding_window)
    hd = cfg.resolved_head_dim
    out = _gqa_attend(q, k, v, lambda qp, kp: keep,
                      pos[:, None], kv_pos, hd ** -0.5)
    proj = jnp.einsum("bshd,hdD->bsD", _head_mask(cfg, out),
                      p["wo"].astype(x.dtype))
    if cross:
        return proj, cache_k, cache_v
    return proj, k, v


def paged_attention(p, cfg, x, kv_entry, page_table, qpos, n_valid,
                    *, kind: str = "attn", impl: str = "auto"):
    """Attention against a paged KV pool (serving decode + chunked prefill).

    x: (A, C, D) — A seats, each advancing by up to C tokens this call
       (C=1 is plain decode; C>1 is one prefill chunk);
    kv_entry: one layer-group's cache entry — ``{"k", "v"}`` pools of
       (P, page, KVH, hd) shared physical pages (page 0 is the scratch
       page: writes from idle seats / chunk padding land there), plus
       ``{"ks", "vs"}`` (P, page, KVH) f32 per-(slot, head) scales when
       the pool stores fp8/int8 (see models.model.init_paged_cache);
    page_table: (A, n) int32 — seat a's logical page i lives in physical
       page page_table[a, i] (dead entries 0);
    qpos: (A, C) int32 absolute position of each token;
    n_valid: (A,) int32 — how many of the C tokens are real.

    impl: 'jnp' gathers pages and runs the dense oracle; 'pallas' streams
    pages through the gather-over-page-table kernel (single-query global
    decode only — chunked prefill and sliding-window layers always take
    the jnp path); 'auto' = pallas on TPU, jnp elsewhere.

    New K/V are scattered into the pool *before* the gather, so token t
    attends to itself and everything earlier.  For quantized pools each
    written token's (KVH, hd) vector is amax-quantized independently and
    its scales scattered with the same indices — write order never
    changes a token's stored bytes.  Returns (out (A, C, D), new_entry).
    """
    A, C, _ = x.shape
    k_pool, v_pool = kv_entry["k"], kv_entry["v"]
    quantized = "ks" in kv_entry
    P, page = k_pool.shape[0], k_pool.shape[1]
    n = page_table.shape[1]
    q, k_new, v_new = _project_qkv(p, cfg, x, x, qpos, qpos)

    valid_tok = jnp.arange(C, dtype=jnp.int32)[None, :] < n_valid[:, None]
    blk = jnp.clip(qpos // page, 0, n - 1)
    phys = jnp.take_along_axis(page_table, blk, axis=1)          # (A, C)
    phys = jnp.where(valid_tok, phys, 0)                         # -> scratch
    off = jnp.where(valid_tok, qpos % page, 0)
    if quantized:
        kv_dtype = "fp8" if k_pool.dtype == jnp.uint8 else "int8"
        kq, ks = mp.quantize_kv_page(k_new, kv_dtype)
        vq, vs = mp.quantize_kv_page(v_new, kv_dtype)
        k_pool = k_pool.at[phys, off].set(kq)
        v_pool = v_pool.at[phys, off].set(vq)
        ks_pool = kv_entry["ks"].at[phys, off].set(ks)
        vs_pool = kv_entry["vs"].at[phys, off].set(vs)
    else:
        k_pool = k_pool.at[phys, off].set(k_new)
        v_pool = v_pool.at[phys, off].set(v_new)

    hd = cfg.resolved_head_dim
    from repro.kernels import ops
    if impl == "auto":
        impl = "pallas" if ops._on_tpu() else "jnp"
    if impl == "pallas" and C == 1 and kind == "attn":
        out = ops.paged_decode_attention(
            q, k_pool, v_pool, page_table, qpos[:, 0] + 1,
            k_scale=ks_pool if quantized else None,
            v_scale=vs_pool if quantized else None)
        out = out.astype(q.dtype)
    else:
        if quantized:
            kd = mp.dequantize_kv_page(k_pool, ks_pool).astype(q.dtype)
            vd = mp.dequantize_kv_page(v_pool, vs_pool).astype(q.dtype)
        else:
            kd, vd = k_pool, v_pool
        k = kd[page_table].reshape(A, n * page, *kd.shape[2:])
        v = vd[page_table].reshape(A, n * page, *vd.shape[2:])
        kv_pos = jnp.broadcast_to(jnp.arange(n * page, dtype=jnp.int32),
                                  (A, n * page))
        keep = kv_pos[:, None, :] <= qpos[:, :, None]            # (A, C, T)
        if kind == "attn_local" and cfg.sliding_window is not None:
            keep &= kv_pos[:, None, :] > (qpos[:, :, None]
                                          - cfg.sliding_window)
        out = _gqa_attend(q, k, v, lambda qp, kp: keep, qpos, kv_pos,
                          hd ** -0.5)
    # a pool stored above the compute dtype (e.g. --kv-dtype f32 under
    # bf16 compute) attends at pool precision; the residual stream stays
    # in compute dtype either way
    out = out.astype(x.dtype)
    proj = jnp.einsum("bshd,hdD->bsD", _head_mask(cfg, out),
                      p["wo"].astype(x.dtype))
    new_entry = ({"k": k_pool, "v": v_pool, "ks": ks_pool, "vs": vs_pool}
                 if quantized else {"k": k_pool, "v": v_pool})
    return proj, new_entry


def ring_decode_attention(p, cfg, x, cache_k, cache_v, pos):
    """Sliding-window decode against a ring buffer of size W = sliding_window.

    The cache keeps only the last W tokens (slot = position mod W), cutting
    local-layer KV memory for long-context decode from O(S) to O(W) — the
    memory-term optimization recorded in EXPERIMENTS.md §Perf.
    """
    W = cache_k.shape[1]
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(p, cfg, x, x, pos[:, None], pos[:, None])
    slot = pos % W
    upd = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, axis=0))
    k = upd(cache_k, k_new, slot)
    v = upd(cache_v, v_new, slot)
    # Absolute position stored in each slot j: pos - ((pos - j) mod W)
    j = jnp.arange(W, dtype=jnp.int32)[None, :]
    kv_pos = pos[:, None] - jnp.mod(pos[:, None] - j, W)
    keep = (kv_pos >= 0)[:, None, :]                        # unfilled slots masked
    hd = cfg.resolved_head_dim
    out = _gqa_attend(q, k, v, lambda qp, kp: keep, pos[:, None], kv_pos, hd ** -0.5)
    proj = jnp.einsum("bshd,hdD->bsD", _head_mask(cfg, out),
                      p["wo"].astype(x.dtype))
    return proj, k, v
