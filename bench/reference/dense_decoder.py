"""Plain reference forward of a dense pre-norm decoder, in float32.

Written from the layer equations of the Llama/Qwen3/MiniCPM family and
independent of the program (it imports nothing from ``repro``):

    x_0      = E[token]
    h        = rms(x) * (1 + g_attn)
    q, k, v  = h Wq, h Wk, h Wv               (per head)
    q, k     = rms(q) * (1 + g_q), rms(k) * (1 + g_k)   (qk_norm only)
    q, k     = rope(q, pos), rope(k, pos)      (rotate-half, base theta)
    a        = softmax(q k^T / sqrt(hd) + causal) v   (head i reads
               key/value head i // (H / KVH))
    x        = x + a Wo
    h        = rms(x) * (1 + g_mlp)
    x        = x + (silu(h Wg) * (h Wu)) Wd
    logits   = (rms(x) * (1 + g_final)) E^T        (tied head)
             = (rms(x) * (1 + g_final)) W_head     (untied: lm_head (D, V))

with rms(x) = x / sqrt(mean(x^2) + eps).  Norm scales are stored as an
offset from 1, as ``shapes`` lays them out.  MiniCPM's scalar
multipliers (scale_emb, scale_depth, dim_model_base) are left out, as
the program leaves them out; the configuration file lists them under
``departures``.

Every matmul runs at ``Precision.HIGHEST`` (full float32 on a TPU).  The
layers run one at a time under ``lax.scan`` with each layer's weights
raised to float32 inside the step, and attention runs in blocks of
query rows, so the reference fits beside the weights.

``fp8=True`` is the control: the same forward with every matmul input
rounded to float8 e4m3 (scaled per vector along the contraction, as an
fp8 serving path would), accumulated in float32.

The module also owns the configuration's weight layout (``shapes``)
and picks its output head from ``params``: the names the harness asks
of a reference module are listed in ``bench/catalog.py``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
Q_BLOCK = 256
HEAD_BLOCK = 256
NORM_STD = 0.1


def shapes(model: dict) -> dict:
    """{path: (shape, std)} for every leaf, in the program's layout
    (``repro.models.model`` ``param_specs`` for a pure-attention decoder
    with one layer kind):

        embed (V, D)                    input embedding (and the tied head)
        lm_head (D, V)                  output head, untied only
        final_norm (D,)
        blocks/pos0/ln1, ln2 (L, D)     RMSNorm offsets, applied as (1 + w)
        blocks/pos0/mixer/wq (L, D, H, hd), wk / wv (L, D, KVH, hd),
                          wo (L, H, hd, D), q_norm / k_norm (L, hd)
        blocks/pos0/mlp/wg / wu (L, D, F), wd (L, F, D)

    Scales: projections N(0, 1/fan_in), the embedding and the untied
    head N(0, 1/D) so the head gives logits of unit spread, norm offsets
    N(0, 0.1^2)."""
    L, D = model["num_layers"], model["d_model"]
    H, KVH = model["num_heads"], model["num_kv_heads"]
    hd, F, V = model["head_dim"], model["d_ff"], model["vocab_size"]
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
    if not model.get("tie_embeddings", False):
        leaves["lm_head"] = ((D, V), 1.0 / math.sqrt(D))
    if model.get("qk_norm", False):
        leaves["blocks/pos0/mixer/q_norm"] = ((L, hd), NORM_STD)
        leaves["blocks/pos0/mixer/k_norm"] = ((L, hd), NORM_STD)
    return leaves


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per vector along
    ``axis`` (the contraction axis of the matmul it feeds)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, fp8, a_axis, b_axis):
    if fp8:
        a, b = _q8(a, a_axis), _q8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g)


def _rope(x, pos, theta):
    """x: (B, T, heads, hd); pos: (B, T)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[..., None, None] * inv      # (B,T,1,hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, fp8):
    """Causal attention in blocks of query rows.
    q: (B, T, H, hd); k, v: (B, T, KVH, hd) -> (B, T, H, hd)."""
    B, T, H, hd = q.shape
    KVH = k.shape[2]
    rep = H // KVH
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qb = min(Q_BLOCK, T)
    nb = T // qb
    qs = q.reshape(B, nb, qb, H, hd).swapaxes(0, 1)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        s = _mm("bqhd,bthd->bhqt", qi, k, fp8, -1, -1) * (hd ** -0.5)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bhqt,bthd->bqhd", p, v, fp8, -1, 1)

    out = jax.lax.map(block, (jnp.arange(nb), qs))
    return out.swapaxes(0, 1).reshape(B, T, H, hd)


def hidden(params, tokens, model: dict, fp8: bool):
    """Final normed hidden states (B, T, D) in float32 for ``tokens``
    (B, T), every row a sequence from position 0."""
    return _hidden(params, tokens, eps=float(model["norm_eps"]),
                   theta=float(model["rope_theta"]), fp8=fp8)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "fp8"))
def _hidden(params, tokens, *, eps: float, theta: float, fp8: bool):
    B, T = tokens.shape
    x = params["embed"][tokens].astype(jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))

    def layer(x, w):
        w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        m = w["mixer"]
        h = _rms(x, w["ln1"], eps)
        q = _mm("btd,dhk->bthk", h, m["wq"], fp8, -1, 0)
        k = _mm("btd,dhk->bthk", h, m["wk"], fp8, -1, 0)
        v = _mm("btd,dhk->bthk", h, m["wv"], fp8, -1, 0)
        if "q_norm" in m:
            q = _rms(q, m["q_norm"], eps)
            k = _rms(k, m["k_norm"], eps)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        a = _attend(q, k, v, fp8)
        B_, T_, H, hd = a.shape
        x = x + _mm("bte,ed->btd", a.reshape(B_, T_, H * hd),
                    m["wo"].reshape(H * hd, -1), fp8, -1, 0)
        h = _rms(x, w["ln2"], eps)
        g = _mm("btd,df->btf", h, w["mlp"]["wg"], fp8, -1, 0)
        u = _mm("btd,df->btf", h, w["mlp"]["wu"], fp8, -1, 0)
        x = x + _mm("btf,fd->btd", jax.nn.silu(g) * u, w["mlp"]["wd"], fp8,
                    -1, 0)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"]["pos0"])
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)


def _head(params):
    """The output head as stored, and whether it is laid out (D, V):
    ``lm_head`` where the configuration unties it, else the embedding
    (V, D)."""
    if "lm_head" in params:
        return params["lm_head"], True
    return params["embed"], False


def head_stats(params, h_ref, targets):
    """Reference logits of rows ``h_ref`` (R, D) against the output
    head: returns (best logit, logit of ``targets``) per row."""
    w, dv = _head(params)
    return _head_stats(w, h_ref, targets, dv=dv)


@functools.partial(jax.jit, static_argnames=("dv",))
def _head_stats(w, h_ref, targets, *, dv: bool):
    E = w.astype(jnp.float32)
    eq = "rd,dv->rv" if dv else "rd,vd->rv"

    def block(args):
        h, t = args
        lg = jnp.einsum(eq, h, E, precision=HIGHEST)
        return (jnp.max(lg, -1),
                jnp.take_along_axis(lg, t[:, None], -1)[:, 0])

    R, D = h_ref.shape
    hb = h_ref.reshape(R // HEAD_BLOCK, HEAD_BLOCK, D)
    tb = targets.reshape(R // HEAD_BLOCK, HEAD_BLOCK)
    best, tgt = jax.lax.map(block, (hb, tb))
    return best.reshape(R), tgt.reshape(R)


def head_argmax_fp8(params, h_ctrl):
    """The control's choice per row: argmax of its fp8 head logits."""
    w, dv = _head(params)
    return _head_argmax_fp8(w, h_ctrl, dv=dv)


@functools.partial(jax.jit, static_argnames=("dv",))
def _head_argmax_fp8(w, h_ctrl, *, dv: bool):
    E = _q8(w.astype(jnp.float32), 0 if dv else -1)
    eq = "rd,dv->rv" if dv else "rd,vd->rv"

    def block(h):
        return jnp.argmax(jnp.einsum(eq, _q8(h, -1), E,
                                     precision=HIGHEST), -1).astype(jnp.int32)

    R, D = h_ctrl.shape
    return jax.lax.map(block, h_ctrl.reshape(R // HEAD_BLOCK, HEAD_BLOCK,
                                             D)).reshape(R)
