"""Multi-head latent attention (MLA, DeepSeek-V2).

Per token, one projection gives a latent ``c`` of ``kv_lora_rank`` lanes
(RMS-normed) and a rope key ``k_pe`` of ``qk_rope_head_dim`` lanes that
every head shares.  The cache holds only the row ``[c ; k_pe]``, padded
with zeros to whole 128-lane rows (``latent_lanes``).  ``kv_b`` maps the
latent to each head's key part ``c W_UK[h]`` (``qk_nope_head_dim``) and
value ``c W_UV[h]`` (``v_head_dim``); head ``h``'s key is
``[c W_UK[h] ; k_pe]`` and its query ``[q_nope[h] ; q_pe[h]]``.

The same function in two forms:

* decompressed (training, prefill): per-head K and V built from the
  latent of every position attended, then plain attention;
* absorbed (decode): ``q_nope[h] W_UK[h]^T`` is a latent query, scores
  are taken against the cached rows themselves, and the softmax-weighted
  latent goes through ``W_UV[h]`` afterwards, so no per-head K or V is
  built for cached tokens.

Softmax scale: ``(qk_nope + qk_rope)^-0.5``, times
``mscale(factor, mscale_all_dim)^2`` under yarn, as DeepSeek's own
modelling code has it.  Rope: rotate-half over the ``qk_rope_head_dim``
lanes (``modules.rope``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.modules import ParamSpec, rms_norm, rope, yarn_mscale

NEG_INF = -1e30
LANES = 128


def mla_specs(cfg) -> dict:
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rp, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq": ParamSpec((d, h, nope + rp), ("embed", "heads", "head_dim")),
        "wkv_a": ParamSpec((d, r + rp), ("embed", None)),
        "kv_norm": ParamSpec((r,), (None,), "zeros"),
        "wkv_b": ParamSpec((r, h, nope + vd), (None, "heads", "head_dim")),
        "wo": ParamSpec((h, vd, d), ("heads", "head_dim", "embed")),
    }


def latent_lanes(cfg) -> int:
    """Width of one cached row: latent and rope key, in whole 128-lane
    rows (a TPU stores the minor dimension so anyway)."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // LANES) * LANES


def softmax_scale(cfg) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    sc = dict(cfg.rope_scaling or ())
    if sc.get("mscale_all_dim"):
        m = yarn_mscale(float(sc["factor"]), float(sc["mscale_all_dim"]))
        s *= m * m
    return s


def _pad_lanes(x, lanes: int):
    pad = lanes - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def project(p, cfg, x, positions):
    """x (B, S, D) -> (q_nope (B, S, H, nope), q_pe (B, S, H, rope) roped,
    the cache rows (B, S, latent_lanes): [c normed ; k_pe roped ; 0])."""
    dt = x.dtype
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    q_pe = rope(q[..., nope:], positions, cfg.rope_theta, cfg.rope_scaling)
    kv = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"].astype(dt))
    c = rms_norm(kv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = rope(kv[..., None, r:], positions, cfg.rope_theta,
                cfg.rope_scaling)[..., 0, :]
    row = _pad_lanes(jnp.concatenate([c, k_pe], -1), latent_lanes(cfg))
    return q[..., :nope], q_pe, row


def _out(p, x_dtype, o):
    """(B, S, H, v_head_dim) -> (B, S, D) through o_proj."""
    return jnp.einsum("bshk,hkD->bsD", o.astype(x_dtype),
                      p["wo"].astype(x_dtype))


def attend_decompressed(p, cfg, q_nope, q_pe, rows, keep, q_chunk: int):
    """Per-head K and V from the cached rows, then attention.

    q_nope/q_pe: (B, Sq, H, *); rows: (B, T, lanes); keep: (B, Sq, T)
    bool.  Queries go in blocks of ``q_chunk`` where that divides Sq, so
    the scores of a long chunk against a long context stay bounded.
    Returns (B, Sq, H, v_head_dim)."""
    dt = q_nope.dtype
    r, nope, rp = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    c, k_pe = rows[..., :r], rows[..., r:r + rp]
    wkv_b = p["wkv_b"].astype(dt)
    k_nope = jnp.einsum("btc,chk->bthk", c, wkv_b[..., :nope])
    v = jnp.einsum("btc,chk->bthk", c, wkv_b[..., nope:])
    scale = softmax_scale(cfg)

    def block(args):
        qn, qp, kp = args
        s = (jnp.einsum("bshk,bthk->bhst", qn, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bshk,btk->bhst", qp, k_pe,
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where(kp[:, None], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(dt)
        return jnp.einsum("bhst,bthk->bshk", probs, v)

    B, Sq = q_nope.shape[:2]
    if Sq <= q_chunk or Sq % q_chunk:
        return block((q_nope, q_pe, keep))
    n = Sq // q_chunk
    split = lambda a: a.reshape(B, n, q_chunk, *a.shape[2:]).swapaxes(0, 1)
    out = jax.lax.map(block, (split(q_nope), split(q_pe), split(keep)))
    return out.swapaxes(0, 1).reshape(B, Sq, *out.shape[3:])


def absorb_query(p, cfg, q_nope, q_pe):
    """The latent query [q_nope W_UK^T ; q_pe ; 0] (B, S, H, lanes)."""
    nope = cfg.qk_nope_head_dim
    ql = jnp.einsum("bshk,chk->bshc", q_nope,
                    p["wkv_b"][..., :nope].astype(q_nope.dtype))
    return _pad_lanes(jnp.concatenate([ql, q_pe], -1), latent_lanes(cfg))


def unabsorb(p, cfg, o_lat):
    """Softmax-weighted latent (B, S, H, kv_lora_rank) through W_UV."""
    return jnp.einsum("bshc,chk->bshk", o_lat,
                      p["wkv_b"][..., cfg.qk_nope_head_dim:].astype(
                          o_lat.dtype))


def attend_latent(cfg, q_lat, rows, keep):
    """Absorbed attention in jnp: q_lat (B, Sq, H, lanes) against the
    cached rows (B, T, lanes) -> softmax-weighted latent (B, Sq, H, r)."""
    s = jnp.einsum("bshl,btl->bhst", q_lat, rows.astype(q_lat.dtype),
                   preferred_element_type=jnp.float32) * softmax_scale(cfg)
    s = jnp.where(keep[:, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(q_lat.dtype)
    return jnp.einsum("bhst,btc->bshc", probs,
                      rows[..., :cfg.kv_lora_rank].astype(q_lat.dtype))


def attention(p, cfg, x, positions, q_chunk: int):
    """Full-sequence causal MLA (training, prefill): (out, cache rows)."""
    q_nope, q_pe, rows = project(p, cfg, x, positions)
    keep = positions[:, None, :] <= positions[:, :, None]
    o = attend_decompressed(p, cfg, q_nope, q_pe, rows, keep, q_chunk)
    return _out(p, x.dtype, o), rows


def decode_attention(p, cfg, x, cache_rows, pos):
    """One token per row against a contiguous cache (B, T, lanes) (the
    slot engine's and the dry-run's decode): writes the token's row at
    ``pos`` and attends in the decompressed form, so it rounds as the
    full-sequence prefill that filled the cache does."""
    q_nope, q_pe, row = project(p, cfg, x, pos[:, None])
    upd = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n, i, axis=0))
    cache_rows = upd(cache_rows, row.astype(cache_rows.dtype), pos)
    T = cache_rows.shape[1]
    keep = (jnp.arange(T)[None, :] <= pos[:, None])[:, None, :]
    o = attend_decompressed(p, cfg, q_nope, q_pe, cache_rows, keep, T)
    return _out(p, x.dtype, o), cache_rows


def paged_attention(p, cfg, x, pool, layer, page_table, qpos, n_valid,
                    *, impl: str = "auto", q_chunk: int = 512):
    """MLA against a latent paged pool (serving decode and chunked prefill).

    x: (A, C, D); pool: (G, P, page, lanes), one row per token of each of
    the G layers of a stack; ``layer``: this layer's index in it (may be
    traced); page_table / qpos / n_valid as ``attention.paged_attention``.
    The chunk's rows are written first, so a token attends to itself.
    C == 1 (decode) takes the absorbed form, through the Pallas latent
    kernel with ``impl`` 'pallas' ('auto': on a TPU); a prefill chunk
    decompresses the seat's latent into per-head K and V.  Returns
    (out (A, C, D), new pool)."""
    A, C, _ = x.shape
    page = pool.shape[2]
    n = page_table.shape[1]
    with jax.named_scope("attn_proj"):
        q_nope, q_pe, rows = project(p, cfg, x, qpos)
    valid_tok = jnp.arange(C, dtype=jnp.int32)[None, :] < n_valid[:, None]
    blk = jnp.clip(qpos // page, 0, n - 1)
    phys = jnp.where(valid_tok, jnp.take_along_axis(page_table, blk, axis=1),
                     0)                                      # -> scratch
    off = jnp.where(valid_tok, qpos % page, 0)
    with jax.named_scope("kv_write"):
        pool = pool.at[layer, phys, off].set(rows.astype(pool.dtype))

    from repro.kernels import ops
    if impl == "auto":
        impl = "pallas" if ops._on_tpu() else "jnp"
    if C == 1:
        with jax.named_scope("attn_proj"), jax.named_scope("mla_absorb"):
            q_lat = absorb_query(p, cfg, q_nope, q_pe)
        if impl == "pallas":
            with jax.named_scope("paged_attention"), \
                    jax.named_scope("latent_attention"):
                o_lat = ops.paged_decode_attention_latent(
                    q_lat, pool, layer, page_table, qpos[:, 0] + 1,
                    v_lanes=cfg.kv_lora_rank, scale=softmax_scale(cfg))
        else:
            seat = pool[layer, page_table].reshape(A, n * page, -1)
            keep = (jnp.arange(n * page)[None, :] <= qpos[:, :1])[:, None]
            o_lat = attend_latent(cfg, q_lat, seat, keep)
        with jax.named_scope("attn_proj"), jax.named_scope("mla_absorb"):
            o = unabsorb(p, cfg, o_lat.astype(x.dtype))
    else:
        seat = pool[layer, page_table].reshape(A, n * page, -1)
        keep = jnp.arange(n * page)[None, None, :] <= qpos[:, :, None]
        o = attend_decompressed(p, cfg, q_nope, q_pe, seat.astype(x.dtype),
                                keep, q_chunk)
    with jax.named_scope("attn_proj"):
        return _out(p, x.dtype, o), pool
