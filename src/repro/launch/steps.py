"""Step-function builders shared by train.py / serve.py / dryrun.py.

One place defines, for every (arch × shape × mesh) cell:
  - the step callable (train_step / prefill_step / decode_step),
  - abstract arguments (ShapeDtypeStructs — nothing allocated),
  - in/out shardings derived from the logical rule table.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.configs.registry import input_specs, input_axes
from repro.models import model as M
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state
from repro.optim.schedules import wsd_schedule
from repro.parallel.sharding import (LogicalRules, DEFAULT_RULES,
                                     activation_rules, rules_for_mesh,
                                     spec_for, spec_for_shape)


@dataclasses.dataclass(frozen=True)
class Cell:
    """Everything the dry-run / launcher needs for one (arch × shape)."""
    name: str
    fn: Any                      # jittable step callable
    abstract_args: Tuple         # pytree of ShapeDtypeStruct
    in_shardings: Tuple
    donate_argnums: Tuple[int, ...]
    rules: LogicalRules
    cfg: ModelConfig
    shape: ShapeConfig


def _tree_shardings(mesh: Mesh, axes_tree, abs_tree, rules: LogicalRules):
    """Shape-aware shardings: axes that don't divide a dim are dropped."""
    is_axes = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)
    return jax.tree.map(
        lambda axes, ab: NamedSharding(
            mesh, spec_for_shape(axes, ab.shape, rules, mesh)),
        axes_tree, abs_tree, is_leaf=is_axes)


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
               opts: Optional[M.RunOptions] = None,
               base_rules: Optional[LogicalRules] = None,
               lr_peak: float = 3e-4, total_steps: int = 10_000,
               pad_heads: Optional[int] = None) -> Cell:
    if pad_heads is not None:
        cfg = dataclasses.replace(cfg, pad_heads_to=pad_heads)
    rules = rules_for_mesh(mesh, base_rules or DEFAULT_RULES)
    rules, seq_sharded = activation_rules(rules, shape.global_batch, mesh)
    opts = opts or M.RunOptions()
    opts = dataclasses.replace(opts, mesh=mesh)
    if shape.is_decode and opts.decode_kv_seq_axis:
        # flash-decoding-style KV partition: the cache seq dim takes every
        # mesh axis the batch doesn't occupy (spec_for_shape auto-drops
        # conflicts), turning the idle model axis into KV capacity.
        rules = rules.with_overrides(seq_shard=("data", "model"))

    from repro.parallel.pipeline import pp_loss_fn, pp_supported
    use_pp = (opts.pipeline and shape.kind == "train"
              and pp_supported(cfg, mesh))
    if use_pp:
        # pipeline stages across the thin 'pod' axis: layer groups shard
        # over pod (layer grads never cross the spine); DP stays on 'data'
        rules = rules.with_overrides(
            layers="pod",
            batch=tuple(a for a in ("data",) if a in mesh.axis_names))

    batch_abs = input_specs(cfg, shape)
    batch_axes = input_axes(cfg, shape, seq_sharded=seq_sharded)
    batch_sh = {k: NamedSharding(mesh, spec_for(batch_axes[k], rules))
                for k in batch_abs}

    specs = M.param_specs(cfg)
    p_axes = M.axes_tree(specs)
    param_dtype = jnp.float32 if shape.kind == "train" else jnp.bfloat16
    params_abs = M.abstract_params(specs, dtype=param_dtype)
    params_sh = _tree_shardings(mesh, p_axes, params_abs, rules)

    name = f"{cfg.name}:{shape.name}"

    if shape.kind == "train":
        compressed = (opts.grad_sync == "compressed"
                      and "pod" in mesh.axis_names)
        opt_abs = {"mu": params_abs, "nu": params_abs,
                   "count": jax.ShapeDtypeStruct((), jnp.int32)}
        opt_sh = {"mu": params_sh, "nu": params_sh,
                  "count": NamedSharding(mesh, P())}
        if compressed:
            # error-feedback residual per parameter shard (fp32)
            opt_abs["ef"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params_abs)
            opt_sh["ef"] = params_sh

        def _value_and_grad(params, batch, inner_rules):
            """Loss+grads, optionally accumulated over k microbatches (scan):
            (or pipelined over the pod axis when opts.pipeline)."""
            if use_pp:
                fn = pp_loss_fn(cfg, mesh, opts, opts.pp_microbatches)
                return jax.value_and_grad(fn, has_aux=True)(params, batch)
            return _value_and_grad_mb(params, batch, inner_rules)

        def _value_and_grad_mb(params, batch, inner_rules):
            """Loss+grads, optionally accumulated over k microbatches (scan):
            peak activation memory ÷k, and the XLA scheduler can overlap
            microbatch i+1's forward with microbatch i's gradient
            reduce-scatters (compute/comm overlap, DESIGN.md §8)."""
            k = opts.microbatches
            if k <= 1 or shape.global_batch % k != 0:
                return jax.value_and_grad(M.lm_loss, has_aux=True)(
                    params, cfg, batch, inner_rules, opts)
            mb = shape.global_batch // k

            def split(x):
                return x.reshape(k, mb, *x.shape[1:])

            batches = jax.tree.map(split, batch)

            def body(acc, mbatch):
                (loss, metrics), grads = jax.value_and_grad(
                    M.lm_loss, has_aux=True)(params, cfg, mbatch,
                                             inner_rules, opts)
                acc_g, acc_l, acc_m = acc
                acc_g = jax.tree.map(lambda a, g: a + g / k, acc_g, grads)
                acc_m = jax.tree.map(lambda a, v: a + v / k, acc_m, metrics)
                return (acc_g, acc_l + loss / k, acc_m), None

            zeros_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            zeros_m = {"xent": jnp.zeros((), jnp.float32),
                       "aux_loss": jnp.zeros((), jnp.float32)}
            (grads, loss, metrics), _ = jax.lax.scan(
                body, (zeros_g, jnp.zeros((), jnp.float32), zeros_m), batches)
            return (loss, metrics), grads

        def _step_body(params, opt_state, batch, inner_rules):
            (loss, metrics), grads = _value_and_grad(params, batch,
                                                     inner_rules)
            lr = wsd_schedule(opt_state["count"], peak=lr_peak,
                              warmup_steps=total_steps // 100,
                              total_steps=total_steps)
            new_p, new_opt, om = adamw_update(grads, opt_state, params, lr)
            return new_p, new_opt, {**metrics, **om, "loss": loss, "lr": lr}

        if compressed:
            # SAKURAONE rail-optimized sync: in-pod reduction happens inside
            # GSPMD (fat ICI links, full precision); the thin cross-pod hop
            # carries int8 payloads + one fp32 scale per tensor, with error
            # feedback (DESIGN.md §8).  The token-embedding gather/scatter is
            # hoisted OUT of the pod-manual region (XLA cannot partition
            # gathers inside manual subgroups); its input-path gradient is
            # chain-ruled outside and synced by XLA's own collective.
            from repro.core.collectives import int8_compress
            inner = rules.with_overrides(
                batch=tuple(a for a in ("data",) if a in mesh.axis_names))
            npods = mesh.shape["pod"]

            def body(params, ef, batch):
                def loss_fn(pp, xe):
                    bb = dict(batch, tok_embeds=xe)
                    return M.lm_loss(pp, cfg, bb, inner, opts)

                (loss, metrics), (gp, gx) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1), has_aux=True)(
                    params, batch["tok_embeds"])

                def sync(g, e):
                    g32 = g.astype(jnp.float32) / npods + e
                    q, s = int8_compress(g32)
                    qs = jax.lax.all_gather(q, "pod", axis=0, tiled=False)
                    ss = jax.lax.all_gather(s, "pod", axis=0, tiled=False)
                    summed = jnp.einsum("p...,p->...",
                                        qs.astype(jnp.float32), ss)
                    return summed.astype(g.dtype), g32 - q.astype(jnp.float32) * s

                flat_g, tdef = jax.tree.flatten(gp)
                flat_e = tdef.flatten_up_to(ef)
                pairs = [sync(g, e) for g, e in zip(flat_g, flat_e)]
                gp = jax.tree.unflatten(tdef, [x[0] for x in pairs])
                new_ef = jax.tree.unflatten(tdef, [x[1] for x in pairs])
                loss = jax.lax.pmean(loss, "pod")
                metrics = jax.tree.map(lambda v: jax.lax.pmean(v, "pod"),
                                       metrics)
                return loss, metrics, gp, new_ef, gx

            def train_step(params, opt_state, batch):
                x_emb = jnp.take(params["embed"], batch["tokens"], axis=0)
                bb = dict(batch, tok_embeds=x_emb)
                in_batch_specs = {k: P("pod") for k in bb}
                fn = jax.shard_map(
                    body, mesh=mesh, axis_names={"pod"},
                    in_specs=(P(), P(), in_batch_specs),
                    out_specs=(P(), P(), P(), P(), P("pod")),
                    check_vma=False)
                loss, metrics, grads, new_ef, gx = fn(
                    params, opt_state["ef"], bb)
                # input-path embedding gradient (global scatter, auto region)
                emb_in = jnp.zeros_like(params["embed"]).at[
                    batch["tokens"].reshape(-1)].add(
                    (gx / npods).reshape(-1, gx.shape[-1]).astype(
                        params["embed"].dtype))
                grads = dict(grads)
                grads["embed"] = grads["embed"] + emb_in
                lr = wsd_schedule(opt_state["count"], peak=lr_peak,
                                  warmup_steps=total_steps // 100,
                                  total_steps=total_steps)
                base_opt = {k: opt_state[k] for k in ("mu", "nu", "count")}
                new_p, new_opt, om = adamw_update(grads, base_opt, params, lr)
                new_opt["ef"] = new_ef
                return new_p, new_opt, {**metrics, **om, "loss": loss, "lr": lr}
        else:
            def train_step(params, opt_state, batch):
                return _step_body(params, opt_state, batch, rules)

        return Cell(name, train_step, (params_abs, opt_abs, batch_abs),
                    (params_sh, opt_sh, batch_sh), (0, 1), rules, cfg, shape)

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            return M.prefill(params, cfg, batch, rules, opts)

        return Cell(name, prefill_step, (params_abs, batch_abs),
                    (params_sh, batch_sh), (), rules, cfg, shape)

    # decode
    cache_abs, cache_axes = M.cache_specs(cfg, shape.global_batch,
                                          shape.seq_len, opts)
    cache_sh = _tree_shardings(mesh, cache_axes, cache_abs, rules)

    def decode_fn(params, cache, tokens, pos):
        return M.decode_step(params, cfg, cache, tokens, pos, rules, opts)

    tok_sh = NamedSharding(mesh, spec_for(("batch", None), rules))
    pos_sh = NamedSharding(mesh, spec_for(("batch",), rules))
    return Cell(name, decode_fn,
                (params_abs, cache_abs, batch_abs["tokens"],
                 jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)),
                (params_sh, cache_sh, tok_sh, pos_sh), (1,), rules, cfg, shape)


def lower_cell(cell: Cell):
    """jit + lower with abstract args (no allocation)."""
    fn = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                 donate_argnums=cell.donate_argnums)
    return fn.lower(*cell.abstract_args)
