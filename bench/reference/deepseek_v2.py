"""Plain reference forward of DeepSeek-V2 (latent attention, routed and
shared experts, leading dense layers, yarn rope), in float32.

Written from the published modelling code (transformers'
``models/deepseek_v2/modeling_deepseek_v2.py`` and DeepSeek's own
``modeling_deepseek.py``) and independent of the program (it imports
nothing from ``repro``).  Per layer, with rms(x) = x / sqrt(mean(x^2) +
eps) and norm scales stored as an offset from 1:

    h            = rms(x) * (1 + g_attn)
    q            = h Wq                        (H heads of nope + rope)
    c, k_pe      = h Wkv_a                     (kv_lora_rank, rope lanes)
    c            = rms(c) * (1 + g_kv)         (the latent only)
    q_pe, k_pe   = rope(q_pe), rope(k_pe)      (k_pe one head, shared)
    k_nope, v    = c Wkv_b                     (per head: nope, v_head_dim)
    a            = softmax([q_nope;q_pe] [k_nope;k_pe]^T * scale + causal) v
    x            = x + a Wo
    h            = rms(x) * (1 + g_mlp)
    x            = x + (silu(h Wg) * (h Wu)) Wd              (leading layers)
    x            = x + sum_{e held} w_e(h) E_e(h) + S(h)     (expert layers)

with ``scale = (nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2``
(DeepSeek's equation; departures in the configuration file), the rope
rotate-half over the rope lanes with yarn's inverse frequencies, w_e the
router's softmax weight of expert e where e is among the token's top_k
(over all ``num_experts``) and 0 elsewhere, summed only over the experts
held here (the first ``experts_held``), and S the ungated
shared expert.  Attention is un-absorbed: per-head K and V are built for
every position.  The output head is the untied ``lm_head``.

Every matmul runs at ``Precision.HIGHEST``; layers run one at a time
under ``lax.scan`` with each layer's weights raised to float32 inside
the step, attention runs in blocks of query rows and the experts one at
a time, so the reference fits beside the weights.  ``fp8=True`` is the
control, as in ``dense_decoder``: every matmul input rounded to float8
e4m3, scaled per vector along the contraction.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

import catalog

_dense = catalog.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "dense_decoder.py"), "bench_reference_dense_decoder")
_mm, _rms = _dense._mm, _dense._rms
head_stats, head_argmax_fp8 = _dense.head_stats, _dense.head_argmax_fp8
HEAD_BLOCK = _dense.HEAD_BLOCK
NORM_STD = _dense.NORM_STD
Q_BLOCK = 128


def shapes(model: dict) -> dict:
    """{path: (shape, std)} for every leaf, in the program's layout
    (``repro.models.model`` ``param_specs``; ``repro.models.mla``):

        embed (V, D), lm_head (D, V), final_norm (D,)
        lead/pos0/...   the k = first_k_dense leading layers, stacked (k, ...)
        blocks/pos0/... the G = num_layers - k expert layers, stacked (G, ...)
          ln1, ln2 (D,)                    RMSNorm offsets, applied as (1 + w)
          mixer/wq (D, H, nope + rope)     q_proj, per head [nope ; rope]
          mixer/wkv_a (D, r + rope)        kv_a_proj_with_mqa, [latent ; k_pe]
          mixer/kv_norm (r,)               kv_a_layernorm offset
          mixer/wkv_b (r, H, nope + v)     kv_b_proj, per head [k_nope ; v]
          mixer/wo (H, v, D)               o_proj
          mlp/wg, wu (D, F), wd (F, D)     leading layers
          moe/router (D, E)                all E routed experts
          moe/wg, wu (Eh, D, Fe), wd (Eh, Fe, D)   the Eh experts held
          moe/shared/wg, wu (D, Fs), wd (Fs, D)    shared experts, ungated

    Scales: projections N(0, 1/fan_in), the embedding and the head
    N(0, 1/D), norm offsets N(0, 0.1^2)."""
    L, D, H = model["num_layers"], model["d_model"], model["num_heads"]
    r, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rp, vd = model["qk_rope_head_dim"], model["v_head_dim"]
    V, F, k = model["vocab_size"], model["d_ff"], model["first_k_dense"]
    mo = model["moe"]
    E, Fe, Fs = mo["num_experts"], mo["d_ff_expert"], mo["d_ff_shared"]
    Eh = mo.get("experts_held") or E
    assert not mo.get("shared_gate", True) and mo["num_shared_experts"]
    sd = lambda n: 1.0 / math.sqrt(n)

    def layer(n):
        return {
            "ln1": ((n, D), NORM_STD), "ln2": ((n, D), NORM_STD),
            "mixer/wq": ((n, D, H, nope + rp), sd(D)),
            "mixer/wkv_a": ((n, D, r + rp), sd(D)),
            "mixer/kv_norm": ((n, r), NORM_STD),
            "mixer/wkv_b": ((n, r, H, nope + vd), sd(r)),
            "mixer/wo": ((n, H, vd, D), sd(H * vd)),
        }

    leaves = {"embed": ((V, D), sd(D)), "lm_head": ((D, V), sd(D)),
              "final_norm": ((D,), NORM_STD)}
    lead = dict(layer(k), **{"mlp/wg": ((k, D, F), sd(D)),
                             "mlp/wu": ((k, D, F), sd(D)),
                             "mlp/wd": ((k, F, D), sd(F))})
    G = L - k
    moe = dict(layer(G), **{
        "moe/router": ((G, D, E), sd(D)),
        "moe/wg": ((G, Eh, D, Fe), sd(D)), "moe/wu": ((G, Eh, D, Fe), sd(D)),
        "moe/wd": ((G, Eh, Fe, D), sd(Fe)),
        "moe/shared/wg": ((G, D, Fs), sd(D)),
        "moe/shared/wu": ((G, D, Fs), sd(D)),
        "moe/shared/wd": ((G, Fs, D), sd(Fs))})
    if k:
        leaves.update({f"lead/pos0/{p}": v for p, v in lead.items()})
    leaves.update({f"blocks/pos0/{p}": v for p, v in moe.items()})
    return leaves


def _mscale(s: float, m: float) -> float:
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def rope_inv_freq(dim: int, theta: float, scaling) -> np.ndarray:
    """Yarn's inverse frequencies (``_compute_yarn_parameters``), or plain
    RoPE's where ``scaling`` is None."""
    base = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return 1.0 / base
    f = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    dim_of = lambda rot: (dim * math.log(orig / (rot * 2 * math.pi))
                          / (2 * math.log(theta)))
    lo = max(math.floor(dim_of(float(scaling.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(dim_of(float(scaling.get("beta_slow", 1)))), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return (1.0 / (f * base)) * ramp + (1.0 / base) * (1.0 - ramp)


def softmax_scale(model: dict) -> float:
    s = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    sc = model.get("rope_scaling") or {}
    if sc.get("mscale_all_dim"):
        s *= _mscale(float(sc["factor"]), float(sc["mscale_all_dim"])) ** 2
    return s


def _rope(x, pos, inv):
    """Rotate-half rope; x: (B, T, heads, d); pos: (B, T)."""
    ang = pos.astype(jnp.float32)[..., None, None] * jnp.asarray(
        inv, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _static(model: dict) -> tuple:
    sc = model.get("rope_scaling") or {}
    attn = (_mscale(float(sc["factor"]), float(sc["mscale"]))
            / _mscale(float(sc["factor"]), float(sc["mscale_all_dim"]))
            if sc.get("mscale") and sc.get("mscale_all_dim") else 1.0)
    assert attn == 1.0, "a yarn cos/sin factor other than 1"
    mo = model["moe"]
    inv = rope_inv_freq(model["qk_rope_head_dim"], float(model["rope_theta"]),
                        sc)
    return (float(model["norm_eps"]), tuple(float(v) for v in inv),
            softmax_scale(model), model["kv_lora_rank"],
            model["qk_nope_head_dim"], int(mo["top_k"]),
            bool(mo.get("norm_topk_prob", True)))


def _attention(x, w, pos, st, fp8):
    eps, inv, scale, r, nope = st[:5]
    m = w["mixer"]
    h = _rms(x, w["ln1"], eps)
    q = _mm("btd,dhk->bthk", h, m["wq"], fp8, -1, 0)
    kv = _mm("btd,dk->btk", h, m["wkv_a"], fp8, -1, 0)
    c = _rms(kv[..., :r], m["kv_norm"], eps)
    q_pe = _rope(q[..., nope:], pos, inv)
    k_pe = _rope(kv[..., None, r:], pos, inv)
    k_nope = _mm("btc,chk->bthk", c, m["wkv_b"][..., :nope], fp8, -1, 0)
    v = _mm("btc,chk->bthk", c, m["wkv_b"][..., nope:], fp8, -1, 0)
    B, T, H, _ = k_nope.shape
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (B, T, H, k_pe.shape[-1]))], -1)
    qf = jnp.concatenate([q[..., :nope], q_pe], -1)
    a = _attend(qf, k, v, scale, fp8)
    vd = v.shape[-1]
    return x + _mm("bte,ed->btd", a.reshape(B, T, H * vd),
                   m["wo"].reshape(H * vd, -1), fp8, -1, 0)


def _attend(q, k, v, scale, fp8):
    """Causal attention in blocks of query rows.  q, k: (B, T, H, dk);
    v: (B, T, H, dv) -> (B, T, H, dv)."""
    B, T, H, dk = q.shape
    qb = min(Q_BLOCK, T)
    qs = q.reshape(B, T // qb, qb, H, dk).swapaxes(0, 1)
    kpos = jnp.arange(T)

    def block(args):
        i, qi = args
        s = _mm("bqhd,bthd->bhqt", qi, k, fp8, -1, -1) * scale
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        return _mm("bhqt,bthd->bqhd", jax.nn.softmax(s, axis=-1), v, fp8,
                   -1, 1)

    out = jax.lax.map(block, (jnp.arange(T // qb), qs))
    return out.swapaxes(0, 1).reshape(B, T, H, v.shape[-1])


def _swiglu(h, wg, wu, wd, fp8):
    g = _mm("btd,df->btf", h, wg, fp8, -1, 0)
    u = _mm("btd,df->btf", h, wu, fp8, -1, 0)
    return _mm("btf,fd->btd", jax.nn.silu(g) * u, wd, fp8, -1, 0)


def _moe(w, h, st, fp8):
    """The expert layer on normed input h (B, T, D): routed over all
    experts, computed for the held ones, plus the shared expert."""
    _, _, _, _, _, top_k, norm = st
    logits = _mm("btd,de->bte", h, w["router"], fp8, -1, 0)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, top_k)
    if norm:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    sh = w["shared"]
    out = _swiglu(h, sh["wg"], sh["wu"], sh["wd"], fp8)

    def expert(acc, ew):
        e, wg, wu, wd = ew
        gate = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)
        return acc + gate[..., None] * _swiglu(h, wg, wu, wd, fp8), None

    held = w["wg"].shape[0]
    out, _ = jax.lax.scan(expert, out, (jnp.arange(held), w["wg"], w["wu"],
                                        w["wd"]))
    return out


def moe(w, h, model: dict, fp8: bool = False):
    """One expert layer's output for normed input ``h`` (B, T, D): the
    layer's ``moe`` weights in float32."""
    with jax.default_matmul_precision("highest"):
        return _moe(w, h, _static(model), fp8)


def hidden(params, tokens, model: dict, fp8: bool):
    """Final normed hidden states (B, T, D) in float32 for ``tokens``
    (B, T), every row a sequence from position 0."""
    with jax.default_matmul_precision("highest"):
        return _hidden(params, tokens, st=_static(model), fp8=fp8)


@functools.partial(jax.jit, static_argnames=("st", "fp8"))
def _hidden(params, tokens, *, st, fp8: bool):
    eps = st[0]
    B, T = tokens.shape
    x = params["embed"][tokens].astype(jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    f32 = lambda w: jax.tree.map(lambda a: a.astype(jnp.float32), w)

    def dense_layer(x, w):
        w = f32(w)
        x = _attention(x, w, pos, st, fp8)
        h = _rms(x, w["ln2"], eps)
        m = w["mlp"]
        return x + _swiglu(h, m["wg"], m["wu"], m["wd"], fp8), None

    def expert_layer(x, w):
        w = f32(w)
        x = _attention(x, w, pos, st, fp8)
        return x + _moe(w["moe"], _rms(x, w["ln2"], eps), st, fp8), None

    if "lead" in params:
        x, _ = jax.lax.scan(dense_layer, x, params["lead"]["pos0"])
    x, _ = jax.lax.scan(expert_layer, x, params["blocks"]["pos0"])
    return _rms(x, params["final_norm"].astype(jnp.float32), eps)
