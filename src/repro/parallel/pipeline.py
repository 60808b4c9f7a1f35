"""Cross-pod pipeline parallelism (GPipe-style, stages = the 'pod' axis).

The SAKURAONE-aware placement: pipeline stages exchange only microbatch
activations (mb·S·D bytes per tick, via ppermute), which is exactly the
kind of thin traffic the paper's 2-pod spine is provisioned for — while
data/tensor parallelism stay on the fat in-pod links.  Layer-group
parameters are sharded over 'pod' (each stage holds G/stages groups), so
layer gradients never cross pods at all.

Schedule: M microbatches, M+stages-1 ticks; every tick each stage applies
its local layer groups to its current input and ppermutes the result
forward.  The loss is computed on the last stage (SPMD-uniform: other
stages compute-and-mask).  Backward is jax.grad through scan+ppermute —
the reverse pipeline falls out of autodiff.  The pipelined region is
manual over every mesh axis, so within it the data and model axes hold
replicas of each stage's compute.

Restrictions (asserted): decoder-only dense/ssm-free archs (no MoE
shard_map nesting, no enc-dec), num_layer_groups % stages == 0.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.sharding import SINGLE_DEVICE_RULES

from repro.models import model as M
from repro.models.modules import rms_norm, softmax_xent_chunked


def pp_supported(cfg, mesh: Mesh) -> bool:
    if "pod" not in mesh.axis_names:
        return False
    if cfg.moe is not None or cfg.encoder_decoder or cfg.attn_period:
        return False
    groups = cfg.num_layers // cfg.scan_period()
    return groups % mesh.shape["pod"] == 0


def pp_loss_fn(cfg, mesh: Mesh, opts, num_microbatches: int):
    """Returns loss(params, batch) with the layer stack pipelined over
    'pod'.  params['blocks'] must be sharded over 'pod' on the group dim
    (rules override 'layers' -> 'pod' — see steps.build_cell)."""
    stages = mesh.shape["pod"]

    def loss(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        mb = B // num_microbatches
        dt = jnp.dtype(cfg.compute_dtype)
        # embedding gather stays OUTSIDE the manual region (XLA cannot
        # partition gathers inside manual subgroups)
        x_emb = jnp.take(params["embed"], tokens, axis=0)
        xs = x_emb.reshape(num_microbatches, mb, S, -1)
        ys = labels.reshape(num_microbatches, mb, S)
        ticks = num_microbatches + stages - 1
        pad = ticks - num_microbatches
        xs_pad = jnp.concatenate(
            [xs, jnp.zeros((pad, *xs.shape[1:]), xs.dtype)], axis=0)
        # labels for the microbatch REACHING the last stage at tick t
        ys_pad = jnp.concatenate(
            [jnp.zeros((pad, *ys.shape[1:]), ys.dtype), ys], axis=0)

        non_block = {k: v for k, v in params.items() if k != "blocks"}

        def body(blocks_local, nb_params, xs_pad, ys_pad):
            stage = jax.lax.axis_index("pod")
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32), (mb, S))
            w_out = (nb_params["embed"].T if cfg.tie_embeddings
                     else nb_params["lm_head"]).astype(dt)

            def stage_fn(x):
                x, _, _ = M.backbone(blocks_local, cfg, x, positions,
                                     SINGLE_DEVICE_RULES, opts, train=True)
                return x

            def tick(carry, inp):
                h_recv, acc_loss, acc_cnt = carry
                x_mb, y_mb, t = inp
                x_in = jnp.where(stage == 0, x_mb.astype(dt), h_recv)
                h_out = stage_fn(x_in)
                h_next = jax.lax.ppermute(
                    h_out, "pod", [(i, i + 1) for i in range(stages - 1)])
                # last stage computes the LM loss for valid ticks
                hn = rms_norm(h_out, nb_params["final_norm"], cfg.norm_eps)
                total, count = softmax_xent_chunked(
                    hn, w_out, y_mb, chunk=opts.xent_chunk)
                valid = jnp.logical_and(stage == stages - 1,
                                        t >= stages - 1).astype(jnp.float32)
                return (h_next, acc_loss + valid * total,
                        acc_cnt + valid * count), None

            h0 = xs_pad[0].astype(dt) * 0
            z0 = h0.reshape(-1)[0].astype(jnp.float32)
            init = (h0, z0, z0)
            (_, tot, cnt), _ = jax.lax.scan(
                tick, init,
                (xs_pad, ys_pad, jnp.arange(ticks, dtype=jnp.int32)))
            tot = jax.lax.psum(tot, "pod")
            cnt = jax.lax.psum(cnt, "pod")
            return tot / jnp.maximum(cnt, 1.0)

        # manual over EVERY mesh axis (XLA's SPMD partitioner aborts on a
        # pod-only partial-manual region around this scan): data/model
        # replicate the stage compute, so the stages run on single-device
        # rules that name no mesh axis
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P("pod"), P(), P(), P()),
            out_specs=P(), check_vma=False)
        out = fn(params["blocks"], non_block, xs_pad, ys_pad)
        return out, {"xent": out, "aux_loss": jnp.zeros((), jnp.float32)}

    return loss
