"""Model assembly: specs, forward (scan over layer blocks), loss, decode.

The layer stack is organized as ``num_layers = G × period`` where ``period``
is the architecture's repeating pattern (1 for homogeneous stacks, 6 for
gemma3's 5-local:1-global, 8 for jamba's 7-mamba:1-attn with MoE every 2).
Parameters for each position in the period are stacked with a leading (G,)
axis and the stack is traversed with ``lax.scan`` — keeping the lowered HLO
small enough that 40 (arch × shape) dry-run cells compile quickly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import mixed_precision
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.modules import (ParamSpec, is_spec, rms_norm, swiglu,
                                  mlp_specs, softmax_xent_chunked,
                                  init_params, abstract_params, axes_tree)
from repro.parallel.sharding import LogicalRules, spec_for
from repro.runtime import sampler as sampler_mod

init_params = init_params          # re-export
abstract_params = abstract_params  # re-export
axes_tree = axes_tree              # re-export


@dataclasses.dataclass(frozen=True)
class RunOptions:
    """Tunable execution options — the perf-hillclimb surface."""
    remat: bool = True
    remat_policy: str = "nothing"    # nothing | dots | none(=no remat)
    q_chunk: int = 1024
    xent_chunk: int = 512
    ring_local_cache: bool = False   # sliding-window layers keep window-sized cache
    aux_loss_weight: float = 0.01
    scan_layers: bool = True
    mesh: Any = None                 # Mesh for shard_map regions (MoE); None on CPU
    moe_impl: str = "capacity"       # capacity (portable) | ragged (TPU gmm)
    paged_attn_impl: str = "auto"    # auto (pallas on TPU, jnp elsewhere) |
                                     # jnp | pallas — serving decode path
    grad_sync: str = "auto"          # auto (GSPMD) | compressed (int8 error-
                                     # feedback on the thin cross-pod hop)
    pipeline: bool = False           # GPipe PP: stages = the 'pod' axis
    pp_microbatches: int = 4
    microbatches: int = 1            # gradient-accumulation microbatches:
                                     # activations shrink ÷k and XLA overlaps
                                     # microbatch i+1 compute with i's grad
                                     # collectives (comm/compute overlap)
    bf16_weights: bool = False       # cast params to bf16 once per step (halves
                                     # FSDP gather traffic + per-use converts)
    decode_kv_seq_axis: bool = False  # shard decode KV cache seq over 'model'


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _block_specs(cfg: ModelConfig, *, decoder_side: bool = True) -> Dict[str, Any]:
    """Specs for ONE period of layers: {'pos0': {...}, 'pos1': {...}, ...}."""
    kinds = cfg.layer_kinds()
    mlps = cfg.mlp_kinds()
    out: Dict[str, Any] = {}
    for i, (kind, mlpk) in enumerate(zip(kinds, mlps)):
        sub: Dict[str, Any] = {"ln1": ParamSpec((cfg.d_model,), ("embed",), "zeros")}
        if kind == "ssm":
            sub["mixer"] = ssm_mod.ssm_specs(cfg)
        else:
            sub["mixer"] = attn_mod.attn_specs(cfg)
        if cfg.encoder_decoder and decoder_side:
            sub["ln_cross"] = ParamSpec((cfg.d_model,), ("embed",), "zeros")
            sub["cross"] = attn_mod.attn_specs(cfg, cross=True)
        if mlpk == "moe":
            sub["ln2"] = ParamSpec((cfg.d_model,), ("embed",), "zeros")
            sub["moe"] = moe_mod.moe_specs(cfg)
        elif mlpk == "dense":
            sub["ln2"] = ParamSpec((cfg.d_model,), ("embed",), "zeros")
            sub["mlp"] = mlp_specs(cfg.d_model, cfg.d_ff)
        out[f"pos{i}"] = sub
    return out


def _stack(specs, g: int):
    return jax.tree.map(
        lambda s: ParamSpec((g,) + s.shape, ("layers",) + s.axes, s.init, s.scale),
        specs, is_leaf=is_spec)


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    period = cfg.scan_period()
    g = cfg.num_layers // period
    specs: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
        "blocks": _stack(_block_specs(cfg), g),
        "final_norm": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    if cfg.encoder_decoder:
        enc_cfg = dataclasses.replace(
            cfg, encoder_decoder=False, moe=None, attn_period=None,
            local_global_period=None, num_layers=cfg.num_encoder_layers)
        specs["encoder"] = {
            "blocks": _stack(_block_specs(enc_cfg, decoder_side=False),
                             cfg.num_encoder_layers),
            "final_norm": ParamSpec((cfg.d_model,), ("embed",), "zeros"),
        }
    if cfg.frontend is not None:
        specs["frontend_proj"] = ParamSpec(
            (cfg.frontend_dim, cfg.d_model), (None, "embed"))
    return specs


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _maybe_bf16(params, opts: "RunOptions"):
    """Optional one-shot bf16 cast of the weights at step entry.  GSPMD then
    moves the convert BEFORE the FSDP all-gathers => half the gather bytes
    and one convert per parameter instead of one per use (§Perf lever)."""
    if not opts.bf16_weights:
        return params
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        params)


def _constraint(x, rules: LogicalRules, axes):
    """Pin ``x`` to the rules' layout.  Rules that map every axis to
    None (one device, or inside a fully manual shard_map region) pin
    nothing; any other constraint needs the caller's mesh in context,
    and one that fails raises."""
    spec = spec_for(axes, rules)
    if all(a is None for a in spec):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def _apply_sublayer(p, cfg, x, kind, mlpk, positions, rules, opts,
                    enc_out=None, want_cache=False):
    """One (mixer + mlp) sublayer in full-sequence mode. Returns (x, aux, cache)."""
    cache = {}
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "ssm":
        mix, (conv_tail, ssm_state) = ssm_mod.ssm_block(p["mixer"], cfg, h)
        if want_cache:
            cache["conv"] = conv_tail
            cache["ssm"] = ssm_state
    else:
        mix, (k, v) = attn_mod.attention(
            p["mixer"], cfg, h, kind=kind, positions=positions,
            q_chunk=opts.q_chunk)
        if want_cache:
            if (kind == "attn_local" and opts.ring_local_cache
                    and cfg.sliding_window and k.shape[1] > cfg.sliding_window):
                k = k[:, -cfg.sliding_window:]
                v = v[:, -cfg.sliding_window:]
            cache["k"], cache["v"] = k, v
    x = x + mix
    if enc_out is not None and "cross" in p:
        h = rms_norm(x, p["ln_cross"], cfg.norm_eps)
        cmix, (ck, cv) = attn_mod.attention(
            p["cross"], cfg, h, x_kv=enc_out, causal=False, q_chunk=opts.q_chunk)
        x = x + cmix
        if want_cache:
            cache["ck"], cache["cv"] = ck, cv
    aux = jnp.zeros((), jnp.float32)
    if mlpk == "moe":
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + moe_mod.moe_block(p["moe"], cfg, h, rules=rules, mesh=opts.mesh,
                                  impl=opts.moe_impl)
        aux = moe_mod.aux_load_balance_loss(p["moe"], cfg, h)
    elif mlpk == "dense":
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + swiglu(h, p["mlp"]["wg"], p["mlp"]["wu"], p["mlp"]["wd"], x.dtype)
    x = _constraint(x, rules, ("batch", "seq_shard", None))
    return x, aux, cache


def backbone(params_blocks, cfg: ModelConfig, x, positions, rules, opts,
             *, enc_out=None, want_cache=False, decoder_side=True,
             train: bool = False):
    """Scan the layer stack. Returns (x, aux_loss_sum, caches or None)."""
    kinds = cfg.layer_kinds()
    mlps = cfg.mlp_kinds()

    def block(carry, blk):
        x, aux = carry
        caches = {}
        for i, (kind, mlpk) in enumerate(zip(kinds, mlps)):
            x, a, c = _apply_sublayer(
                blk[f"pos{i}"], cfg, x, kind, mlpk, positions, rules, opts,
                enc_out=enc_out if decoder_side else None,
                want_cache=want_cache)
            aux = aux + a
            if want_cache:
                caches[f"pos{i}"] = c
        return (x, aux), (caches if want_cache else None)

    if train and opts.remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if opts.remat_policy == "dots" else None)
        block = jax.checkpoint(block, policy=policy, prevent_cse=False)

    (x, aux), caches = jax.lax.scan(block, (x, jnp.zeros((), jnp.float32)),
                                    params_blocks)
    return x, aux, caches


def _embed_inputs(params, cfg: ModelConfig, batch, rules):
    """Token (+ modality stub) embedding. Returns (x, positions, enc_out)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    dt = jnp.dtype(cfg.compute_dtype)
    if "tok_embeds" in batch:
        # precomputed embeddings (the compressed grad-sync path hoists the
        # gather out of the pod-manual shard_map region — XLA's partitioner
        # cannot partition gathers inside manual subgroups)
        x = batch["tok_embeds"].astype(dt)
    else:
        x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cfg.frontend == "vision" and "patches" in batch:
        proj = jnp.einsum("bpf,fd->bpd", batch["patches"].astype(dt),
                          params["frontend_proj"].astype(dt))
        x = jax.lax.dynamic_update_slice(x, proj, (0, 0, 0))
    x = _constraint(x, rules, ("batch", "seq_shard", None))
    enc_out = None
    if cfg.encoder_decoder:
        frames = batch["audio"]  # (B, L_enc, frontend_dim) — stub embeddings
        e = jnp.einsum("blf,fd->bld", frames.astype(dt),
                       params["frontend_proj"].astype(dt))
        enc_pos = jnp.broadcast_to(
            jnp.arange(e.shape[1], dtype=jnp.int32), (B, e.shape[1]))
        enc_cfg = dataclasses.replace(
            cfg, encoder_decoder=False, moe=None, attn_period=None,
            local_global_period=None, num_layers=cfg.num_encoder_layers)

        def enc_block(h, blk):
            hh = rms_norm(h, blk["pos0"]["ln1"], cfg.norm_eps)
            mix, _ = attn_mod.attention(blk["pos0"]["mixer"], enc_cfg, hh,
                                        causal=False, positions=enc_pos)
            h = h + mix
            hh = rms_norm(h, blk["pos0"]["ln2"], cfg.norm_eps)
            h = h + swiglu(hh, blk["pos0"]["mlp"]["wg"], blk["pos0"]["mlp"]["wu"],
                           blk["pos0"]["mlp"]["wd"], h.dtype)
            return h, None

        e, _ = jax.lax.scan(enc_block, e, params["encoder"]["blocks"])
        enc_out = rms_norm(e, params["encoder"]["final_norm"], cfg.norm_eps)
    return x, positions, enc_out


def _output_weight(params, cfg):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


def lm_loss(params, cfg: ModelConfig, batch, rules: LogicalRules,
            opts: RunOptions = RunOptions()) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Mean next-token cross-entropy (+ MoE aux loss)."""
    params = _maybe_bf16(params, opts)
    x, positions, enc_out = _embed_inputs(params, cfg, batch, rules)
    x, aux, _ = backbone(params["blocks"], cfg, x, positions, rules, opts,
                         enc_out=enc_out, train=True)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    total, count = softmax_xent_chunked(
        x, _output_weight(params, cfg).astype(x.dtype), batch["labels"],
        chunk=opts.xent_chunk)
    loss = total / jnp.maximum(count, 1.0)
    metrics = {"xent": loss, "aux_loss": aux}
    if cfg.moe is not None:
        loss = loss + opts.aux_loss_weight * aux
    return loss, metrics


def prefill(params, cfg: ModelConfig, batch, rules: LogicalRules,
            opts: RunOptions = RunOptions()):
    """Run the prompt through the model; return (last_logits, cache)."""
    x, positions, enc_out = _embed_inputs(params, cfg, batch, rules)
    x, _, caches = backbone(params["blocks"], cfg, x, positions, rules, opts,
                            enc_out=enc_out, want_cache=True)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = x[:, -1:]
    logits = jnp.einsum("bsd,dv->bsv", last,
                        _output_weight(params, cfg).astype(x.dtype))
    return logits.astype(jnp.float32), caches


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def cache_entry_shapes(cfg: ModelConfig, pos_idx: int, batch: int, seq: int,
                       opts: RunOptions = RunOptions()):
    """Shape/axes template for one period-position's cache entry."""
    kinds = cfg.layer_kinds()
    kvh, hd = cfg.padded_kv_heads, cfg.resolved_head_dim
    kind = kinds[pos_idx]
    ent: Dict[str, Tuple[Tuple[int, ...], Tuple[Optional[str], ...]]] = {}
    if kind == "ssm":
        d_inner, nheads, conv_dim = ssm_mod.ssm_dims(cfg)
        w = cfg.ssm.conv_width
        ent["conv"] = ((batch, w - 1, conv_dim), ("batch", None, "ssm_inner"))
        ent["ssm"] = ((batch, nheads, cfg.ssm.d_state, cfg.ssm.head_dim),
                      ("batch", "ssm_heads", None, None))
    else:
        t = seq
        if kind == "attn_local" and opts.ring_local_cache and cfg.sliding_window:
            t = min(seq, cfg.sliding_window)
        ent["k"] = ((batch, t, kvh, hd), ("batch", "seq_shard", "kv_heads", None))
        ent["v"] = ((batch, t, kvh, hd), ("batch", "seq_shard", "kv_heads", None))
    if cfg.encoder_decoder:
        ent["ck"] = ((batch, cfg.encoder_len, kvh, hd),
                     ("batch", None, "kv_heads", None))
        ent["cv"] = ((batch, cfg.encoder_len, kvh, hd),
                     ("batch", None, "kv_heads", None))
    return ent


def cache_specs(cfg: ModelConfig, batch: int, seq: int,
                opts: RunOptions = RunOptions()):
    """(abstract_cache, axes_tree) for decode-cell dry-runs."""
    period = cfg.scan_period()
    g = cfg.num_layers // period
    dt = jnp.dtype(cfg.compute_dtype)
    shapes, axes = {}, {}
    for i in range(period):
        ent = cache_entry_shapes(cfg, i, batch, seq, opts)
        shapes[f"pos{i}"] = {
            k: jax.ShapeDtypeStruct((g,) + s,
                                    jnp.float32 if k in ("ssm",) else dt)
            for k, (s, _) in ent.items()}
        axes[f"pos{i}"] = {k: ("layers",) + a for k, (_, a) in ent.items()}
    return shapes, axes


def init_cache(cfg: ModelConfig, batch: int, seq: int,
               opts: RunOptions = RunOptions()):
    """Zero-initialized cache (smoke tests / serving)."""
    shapes, _ = cache_specs(cfg, batch, seq, opts)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def paged_cache_supported(cfg: ModelConfig) -> bool:
    """The paged layout covers pure-attention decoders (global and
    sliding-window layers).  SSM state and encoder K/V are fixed-size per
    request — nothing to page — so those archs stay on the slot engine."""
    return (not cfg.encoder_decoder and cfg.frontend is None
            and all(k in ("attn", "attn_local") for k in cfg.layer_kinds()))


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     kv_dtype: Optional[str] = None):
    """Zero paged KV pools: {posN: {k,v: (G, num_pages, page, KVH, hd)}}.

    ``num_pages`` counts *physical* pages including the reserved scratch
    page 0 (see runtime.paged_kv.BlockManager).

    ``kv_dtype`` picks the pool's storage precision (one of
    ``core.mixed_precision.KV_DTYPES``); None keeps the config's compute
    dtype — the pre-quantization layout, bit-for-bit.  Quantized dtypes
    (fp8/int8) add f32 ``ks``/``vs`` scale leaves of
    (G, num_pages, page, KVH) — one scale per stored (token, head)
    vector, page-adjacent so copy-on-write and donation treat values
    and scales as one pytree."""
    assert paged_cache_supported(cfg), cfg.name
    period = cfg.scan_period()
    g = cfg.num_layers // period
    kvh, hd = cfg.padded_kv_heads, cfg.resolved_head_dim
    if kv_dtype is None:
        dt, quantized = jnp.dtype(cfg.compute_dtype), False
    else:
        dt = jnp.dtype(mixed_precision.kv_storage_dtype(kv_dtype))
        quantized = mixed_precision.kv_is_quantized(kv_dtype)
    shape = (g, num_pages, page_size, kvh, hd)

    def entry():
        ent = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        if quantized:
            sshape = (g, num_pages, page_size, kvh)
            ent["ks"] = jnp.zeros(sshape, jnp.float32)
            ent["vs"] = jnp.zeros(sshape, jnp.float32)
        return ent

    return {f"pos{i}": entry() for i in range(period)}


def paged_page_bytes(cfg: ModelConfig, page_size: int,
                     kv_dtype: Optional[str] = None) -> int:
    """Bytes one physical page costs across the whole paged cache (all
    layers, K and V, values plus scales for quantized dtypes) — the
    figure byte-denominated budget accounting compares across engines
    of different precisions (runtime.router.HostBudget)."""
    kvh, hd = cfg.padded_kv_heads, cfg.resolved_head_dim
    if kv_dtype is None:
        tok = kvh * hd * jnp.dtype(cfg.compute_dtype).itemsize
    else:
        tok = kvh * mixed_precision.kv_token_bytes(kv_dtype, hd)
    return cfg.num_layers * page_size * tok * 2      # K and V


def copy_paged_page(cache, src, dst):
    """Copy physical page ``src`` onto page ``dst`` in every K/V pool of a
    paged cache (prefix-cache copy-on-write: a request that shares only
    part of a cached page gets its own copy to write its tail into).

    ``src``/``dst`` may be traced scalars; jit-compatible.  ``src == dst``
    is a no-op: callers jit this with the pool donated, and an aliased
    self-copy must not read from the buffer it is overwriting.
    """
    return jax.lax.cond(
        jnp.asarray(src) == jnp.asarray(dst),
        lambda c: c,
        lambda c: jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), c),
        cache)


def paged_decode_step(params, cfg: ModelConfig, cache, tokens, pos,
                      page_table, n_valid, rules: LogicalRules,
                      opts: RunOptions = RunOptions()):
    """Advance every seat by up to C tokens against the paged KV pool.

    tokens: (A, C) int32 (C=1: batched decode; C>1: one prefill chunk);
    pos: (A,) int32 first position of each seat's chunk;
    page_table: (A, n) int32 logical->physical page map;
    n_valid: (A,) int32 valid tokens per seat (0 = idle seat; its writes
    are routed to the scratch page and its logits are garbage).

    Returns (logits (A, C, V) fp32, new_cache).
    """
    kinds = cfg.layer_kinds()
    mlps = cfg.mlp_kinds()
    dt = jnp.dtype(cfg.compute_dtype)
    A, C = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    x = _constraint(x, rules, ("batch", None, None))
    qpos = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]

    def block(x, blk_and_cache):
        blk, cac = blk_and_cache
        new_cac = {}
        for i, (kind, mlpk) in enumerate(zip(kinds, mlps)):
            p = blk[f"pos{i}"]
            c = cac[f"pos{i}"]
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            mix, nc = attn_mod.paged_attention(
                p["mixer"], cfg, h, c, page_table, qpos,
                n_valid, kind=kind, impl=opts.paged_attn_impl)
            x = x + mix
            if mlpk == "moe":
                hh = rms_norm(x, p["ln2"], cfg.norm_eps)
                x = x + moe_mod.moe_block(p["moe"], cfg, hh, rules=rules,
                                          mesh=opts.mesh,
                                          xaxes=("batch", None, None),
                                          impl=opts.moe_impl)
            elif mlpk == "dense":
                hh = rms_norm(x, p["ln2"], cfg.norm_eps)
                x = x + swiglu(hh, p["mlp"]["wg"], p["mlp"]["wu"],
                               p["mlp"]["wd"], x.dtype)
            new_cac[f"pos{i}"] = nc
        return x, new_cac

    x, new_cache = jax.lax.scan(
        lambda carry, xs: block(carry, xs), x, (params["blocks"], cache))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x,
                        _output_weight(params, cfg).astype(x.dtype))
    return logits.astype(jnp.float32), new_cache


def fused_decode_tick(params, cfg: ModelConfig, cache, last_tok, pos,
                      page_table, n_valid, temperature, top_k, top_p,
                      seed, rid, step, rules: LogicalRules,
                      opts: RunOptions = RunOptions()):
    """One whole serving decode tick as a single device dispatch.

    Runs the batched paged model step AND batched sampling (greedy
    argmax / temperature / top-k / top-p via
    ``runtime.sampler.sample_tokens``, keyed per ``(seed, rid, step)``)
    on device, then advances every active seat's position, sampler step
    and last-token slot functionally — so the serving state lives on
    the device between ticks and exactly one ``(A,)`` int32 token
    vector crosses to the host per tick.  Idle seats (``n_valid == 0``)
    ride through with their state unchanged.

    last_tok: (A,) int32 — each seat's previously emitted token (the
    tick's model input);
    pos: (A,) int32 next write position per seat;
    page_table: (A, n) int32 logical->physical page map;
    n_valid: (A,) int32 — 1 for seats decoding this tick, else 0;
    temperature/top_p: (A,) float32, top_k: (A,) int32,
    seed/rid/step: (A,) uint32 — per-seat sampling state.

    Returns ``(tokens, new_cache, new_pos, new_step, page_table)``:
    ``tokens`` is both the tick's emission and the next tick's
    ``last_tok`` (inactive seats keep their previous token), and
    ``page_table`` is returned untouched so callers can donate it.
    """
    logits, new_cache = paged_decode_step(
        params, cfg, cache, last_tok[:, None], pos, page_table, n_valid,
        rules, opts)
    toks = sampler_mod.sample_tokens(logits[:, 0], temperature, top_k,
                                     top_p, seed, rid, step)
    active = n_valid > 0
    toks = jnp.where(active, toks, last_tok)
    new_pos = pos + n_valid
    new_step = step + n_valid.astype(step.dtype)
    return toks, new_cache, new_pos, new_step, page_table


def decode_step(params, cfg: ModelConfig, cache, tokens, pos,
                rules: LogicalRules, opts: RunOptions = RunOptions()):
    """One token step. tokens: (B,1) int32; pos: (B,) int32 (next position).

    Returns (logits (B,1,V) fp32, new_cache).
    """
    kinds = cfg.layer_kinds()
    mlps = cfg.mlp_kinds()
    dt = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    x = _constraint(x, rules, ("batch", None, None))

    def block(x, blk_and_cache):
        blk, cac = blk_and_cache
        new_cac = {}
        for i, (kind, mlpk) in enumerate(zip(kinds, mlps)):
            p = blk[f"pos{i}"]
            c = cac[f"pos{i}"]
            nc = {}
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            if kind == "ssm":
                mix, (conv, ssm) = ssm_mod.ssm_block(
                    p["mixer"], cfg, h, conv_state=c["conv"],
                    ssm_state=c["ssm"], decode=True)
                nc["conv"], nc["ssm"] = conv, ssm
            else:
                if (kind == "attn_local" and opts.ring_local_cache
                        and cfg.sliding_window
                        and c["k"].shape[1] == cfg.sliding_window):
                    mix, k, v = attn_mod.ring_decode_attention(
                        p["mixer"], cfg, h, c["k"], c["v"], pos)
                else:
                    mix, k, v = attn_mod.decode_attention(
                        p["mixer"], cfg, h, c["k"], c["v"], pos, kind=kind)
                nc["k"], nc["v"] = k, v
            x = x + mix
            if cfg.encoder_decoder:
                hh = rms_norm(x, p["ln_cross"], cfg.norm_eps)
                cmix, _, _ = attn_mod.decode_attention(
                    p["cross"], cfg, hh, c["ck"], c["cv"], pos, cross=True)
                x = x + cmix
                nc["ck"], nc["cv"] = c["ck"], c["cv"]
            if mlpk == "moe":
                hh = rms_norm(x, p["ln2"], cfg.norm_eps)
                x = x + moe_mod.moe_block(p["moe"], cfg, hh, rules=rules,
                                          mesh=opts.mesh,
                                          xaxes=("batch", None, None),
                                          impl=opts.moe_impl)
            elif mlpk == "dense":
                hh = rms_norm(x, p["ln2"], cfg.norm_eps)
                x = x + swiglu(hh, p["mlp"]["wg"], p["mlp"]["wu"],
                               p["mlp"]["wd"], x.dtype)
            new_cac[f"pos{i}"] = nc
        return x, new_cac

    x, new_cache = jax.lax.scan(
        lambda carry, xs: block(carry, xs), x, (params["blocks"], cache))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x,
                        _output_weight(params, cfg).astype(x.dtype))
    return logits.astype(jnp.float32), new_cache
