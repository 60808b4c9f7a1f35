"""Pallas TPU kernel: flash attention (online-softmax tiled attention).

The prefill hot spot.  Q/K/V tiles are staged HBM->VMEM with MXU-aligned
BlockSpecs; softmax statistics (running max / normalizer) and the output
accumulator live in fp32 VMEM scratch across the KV grid dimension, so the
(Sq × Skv) score matrix is never materialized — the memory-term fix that
lets 32k-prefill run without O(S²) intermediates.

Supports causal masking and sliding-window (local) attention — the gemma3
5:1 local:global pattern runs both variants.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int | None,
                  bq: int, bk: int, k_steps: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # Skip fully-masked tiles (upper-triangle blocks under causal masking).
    run = jnp.asarray(True)
    if causal:
        run = (ki * bk) <= (qi * bq + bq - 1)
    if window is not None:
        run = jnp.logical_and(run, (ki + 1) * bk - 1 > qi * bq - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        keep = k_pos < kv_len                   # mask zero-padding tail
        if causal:
            keep &= k_pos <= q_pos
        if window is not None:
            keep &= k_pos > q_pos - window
        s = jnp.where(keep, s, NEG_INF)

        m_old = m_ref[...]                                  # (bq, 1)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_old - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret", "kv_len"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int | None = None, bq: int = 128,
                           bk: int = 128, interpret: bool = False,
                           kv_len: int | None = None):
    """q: (BH, Sq, d); k/v: (BH, Skv, d). Returns (BH, Sq, d) in q.dtype.

    BH is the flattened batch×heads dim (GQA head expansion happens in the
    ops.py wrapper).  Sq % bq == 0 and Skv % bk == 0 (wrapper pads).
    """
    bh, sq, d = q.shape
    _, skv, _ = k.shape
    assert sq % bq == 0 and skv % bk == 0, (sq, skv, bq, bk)
    k_steps = skv // bk
    scale = d ** -0.5
    kv_len = kv_len if kv_len is not None else skv

    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, k_steps=k_steps,
                          kv_len=kv_len),
        grid=(bh, sq // bq, k_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, s: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, s: (b, s, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, s: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, s: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
