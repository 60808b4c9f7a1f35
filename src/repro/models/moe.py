"""Mixture-of-experts block: dropless routing via jax.lax.ragged_dot.

Tokens are routed top-k, replicated k times, sorted by expert id, and pushed
through grouped GEMMs (``ragged_dot``) — the TPU-native analogue of
megablocks.  Sharding strategy (DESIGN.md §5): the expert FFN hidden dim is
tensor-parallel over the ``model`` axis ("MoE-TP"), which divides evenly for
any expert count (60, 16, 8) on the fixed 16-wide model axis; routing + sort
stay *local* to each data shard, expressed with ``jax.shard_map`` so no
global token sort ever crosses the network (true expert-parallel all-to-all
is a recorded perf-iteration alternative).

Compute is per routed token only (top_k × T), so HLO FLOPs track
6·N_active·D for the roofline's MoE model-FLOPs line.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.modules import ParamSpec, swiglu
from repro.parallel.sharding import spec_for


def moe_specs(cfg) -> dict:
    m = cfg.moe
    d = cfg.d_model
    specs = {
        "router": ParamSpec((d, m.num_experts), ("embed", "expert")),
        "wg": ParamSpec((m.num_experts, d, m.d_ff_expert), ("expert", "embed", "expert_ff")),
        "wu": ParamSpec((m.num_experts, d, m.d_ff_expert), ("expert", "embed", "expert_ff")),
        "wd": ParamSpec((m.num_experts, m.d_ff_expert, d), ("expert", "expert_ff", "embed")),
    }
    if m.num_shared_experts:
        f_sh = m.d_ff_shared or m.num_shared_experts * m.d_ff_expert
        specs["shared"] = {
            "wg": ParamSpec((d, f_sh), ("embed", "ff")),
            "wu": ParamSpec((d, f_sh), ("embed", "ff")),
            "wd": ParamSpec((f_sh, d), ("ff", "embed")),
            "gate": ParamSpec((d, 1), ("embed", None)),
        }
    return specs


def _expert_gemms_ragged(p, m, xs, group_sizes, dt):
    """Dropless grouped GEMMs via ragged_dot.  On TPU this lowers to the
    native grouped-matmul (megablocks-style); on CPU/GPU XLA falls back to
    one DENSE (T·k, D)×(D, F) dot per expert — E/k× the true FLOPs — so the
    dry-run uses the capacity path below for honest compiled cost."""
    g = jax.lax.ragged_dot(xs, p["wg"].astype(dt), group_sizes)
    u = jax.lax.ragged_dot(xs, p["wu"].astype(dt), group_sizes)
    h = jax.nn.silu(g) * u
    return jax.lax.ragged_dot(h, p["wd"].astype(dt), group_sizes)


def _expert_gemms_capacity(p, m, xs, group_sizes, dt):
    """Capacity-based expert GEMMs, batched-einsum formulation (GShard):
    expert e reads the C-slot window of the sorted token array at its
    group offset (one gather), all experts' FFNs run as ONE batched GEMM
    einsum('ecd,edf->ecf'), results scatter back to their sorted slots.

    Compiled FLOPs = cf × the true grouped FLOPs on every backend (the
    honest dry-run cost ragged_dot's dense fallback can't give); tokens
    beyond an expert's capacity are dropped (exact when cf covers the max
    group size).  No scan => no O(E·|buffer|) carry traffic in backward.
    """
    TK, D = xs.shape
    E = m.num_experts
    C = int(m.capacity_factor * TK / E) + 1
    C = min(max((C + 7) // 8 * 8, 8), TK)      # pad to 8, bound by TK
    offsets = jnp.cumsum(group_sizes) - group_sizes            # (E,)
    slot = offsets[:, None] + jnp.arange(C)[None, :]           # (E, C)
    valid = jnp.arange(C)[None, :] < group_sizes[:, None]      # (E, C)
    idx = jnp.clip(slot, 0, TK - 1)
    xe = jnp.take(xs, idx.reshape(-1), axis=0).reshape(E, C, D)
    xe = xe * valid[..., None].astype(dt)

    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"].astype(dt))
    u = jnp.einsum("ecd,edf->ecf", xe, p["wu"].astype(dt))
    ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["wd"].astype(dt))
    ye = ye * valid[..., None].astype(dt)

    # each sorted slot belongs to exactly one (e, c) cell
    out = jnp.zeros((TK, D), dt).at[idx.reshape(-1)].add(
        ye.reshape(-1, D) * valid.reshape(-1, 1).astype(dt))
    return out


def _moe_local(p, cfg, x, *, psum_axis=None, impl: str = "capacity"):
    """Local (per-shard) MoE. x: (B, S, D) -> (B, S, D).

    impl: 'capacity' (portable, honest FLOPs, capacity drops) or
          'ragged' (dropless ragged_dot — the TPU production path).
    """
    m = cfg.moe
    B, S, D = x.shape
    dt = x.dtype
    xt = x.reshape(B * S, D)
    T = B * S

    logits = jnp.einsum("td,de->te", xt, p["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, m.top_k)               # (T, k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    flat_expert = idx.reshape(-1)                               # (T*k,)
    sort_idx = jnp.argsort(flat_expert)                         # stable
    tok_ids = sort_idx // m.top_k                               # source token per slot
    xs = jnp.take(xt, tok_ids, axis=0)                          # (T*k, D)
    group_sizes = jnp.bincount(flat_expert, length=m.num_experts).astype(jnp.int32)

    if impl == "ragged":
        y = _expert_gemms_ragged(p, m, xs, group_sizes, dt)
    else:
        y = _expert_gemms_capacity(p, m, xs, group_sizes, dt)

    w_sorted = jnp.take(weights.reshape(-1), sort_idx, axis=0).astype(dt)
    out = jnp.zeros((T, D), dt).at[tok_ids].add(y * w_sorted[:, None])

    if "shared" in p:
        sh = p["shared"]
        ys = swiglu(xt, sh["wg"], sh["wu"], sh["wd"], dt)
        gate = jax.nn.sigmoid(
            jnp.einsum("td,dz->tz", xt, sh["gate"].astype(dt)).astype(jnp.float32))
        out = out + ys * gate.astype(dt)

    if psum_axis is not None:
        out = jax.lax.psum(out, psum_axis)
    return out.reshape(B, S, D)


def moe_block(p, cfg, x, rules=None, mesh=None,
              xaxes=("batch", "seq_shard", None), impl: str = "capacity"):
    """Sharded MoE: shard_map keeps routing local, TPs the expert FFN dim.

    Falls back to the plain local implementation when no mesh is given
    (single-device smoke tests).
    """
    if mesh is None or mesh.size == 1 or "model" not in mesh.axis_names:
        return _moe_local(p, cfg, x, impl=impl)

    xspec = spec_for(xaxes, rules)
    # Partition specs for the weights (same table the params are laid out by).
    pspec = {
        "router": spec_for(("embed", "expert"), rules),
        "wg": spec_for(("expert", "embed", "expert_ff"), rules),
        "wu": spec_for(("expert", "embed", "expert_ff"), rules),
        "wd": spec_for(("expert", "expert_ff", "embed"), rules),
    }
    if "shared" in p:
        pspec["shared"] = {
            "wg": spec_for(("embed", "ff"), rules),
            "wu": spec_for(("embed", "ff"), rules),
            "wd": spec_for(("ff", "embed"), rules),
            "gate": spec_for(("embed", None), rules),
        }

    # FSDP: if the "embed" (d_model) weight dim is sharded, gather it inside
    # the shard_map body before use (manual regions don't get GSPMD's
    # automatic ZeRO gathers).
    emb = rules.mesh_axes("embed")
    emb_axes = (emb,) if isinstance(emb, str) else (emb or ())
    emb_axes = tuple(a for a in emb_axes if a in mesh.axis_names)
    # embed-dim position within each weight's shape
    EMB_DIM = {"router": 0, "wg": 1, "wu": 1, "wd": 2}
    EMB_DIM_SHARED = {"wg": 0, "wu": 0, "wd": 1, "gate": 0}

    def gather_emb(w, dim):
        for a in emb_axes:
            w = jax.lax.all_gather(w, a, axis=dim, tiled=True)
        return w

    def body(pp, xx):
        if emb_axes:
            pp = dict(pp)
            for k2, d2 in EMB_DIM.items():
                pp[k2] = gather_emb(pp[k2], d2)
            if "shared" in pp:
                pp["shared"] = {k2: gather_emb(v2, EMB_DIM_SHARED[k2])
                                for k2, v2 in pp["shared"].items()}
        return _moe_local(pp, cfg, xx, psum_axis="model", impl=impl)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(pspec, xspec),
                       out_specs=xspec, check_vma=False)
    return fn(p, x)


def aux_load_balance_loss(p, cfg, x) -> jnp.ndarray:
    """Switch-style load-balancing auxiliary loss (fraction × probability)."""
    m = cfg.moe
    xt = x.reshape(-1, x.shape[-1])
    logits = jnp.einsum("td,de->te", xt, p["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(probs, m.top_k)
    counts = jnp.sum(jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32), axis=(0, 1))
    frac = counts / jnp.maximum(jnp.sum(counts), 1.0)
    imp = jnp.mean(probs, axis=0)
    return m.num_experts * jnp.sum(frac * imp)
