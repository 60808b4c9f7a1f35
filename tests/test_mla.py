"""Multi-head latent attention (DeepSeek-V2): the absorbed decode form
equals the un-absorbed one, the latent paged kernel equals the jnp path
under the TPU interpreter, and yarn's frequencies and the mscale^2
softmax scale follow the published formulas."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config, reduced_config
from repro.kernels.decode_attention import paged_decode_attention_latent_pallas
from repro.models import mla
from repro.models.modules import init_params, rope_frequencies


def _cfg():
    return reduced_config(get_config("deepseek-v2-lite"),
                          compute_dtype="float32")


def test_absorbed_decode_equals_unabsorbed():
    """Scores against the cached latent with q_nope W_UK^T, then W_UV on
    the weighted latent, is the same function as per-head K and V built
    from the latent: f32 on both sides, so only the order of summation
    differs."""
    cfg = _cfg()
    p = init_params(mla.mla_specs(cfg), jax.random.PRNGKey(0))
    T, B = 37, 3
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q_nope, q_pe, rows = mla.project(p, cfg, x, pos)
    lens = jnp.asarray([T, 20, 1])
    keep = (jnp.arange(T)[None, :] < lens[:, None])[:, None, :]
    last = lens - 1
    qn = q_nope[jnp.arange(B), last][:, None]
    qp = q_pe[jnp.arange(B), last][:, None]
    want = mla.attend_decompressed(p, cfg, qn, qp, rows, keep, 512)
    got = mla.unabsorb(p, cfg, mla.attend_latent(
        cfg, mla.absorb_query(p, cfg, qn, qp), rows, keep))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_latent_kernel_matches_jnp(dtype):
    """The kernel's page gather (one DMA a live page, double-buffered),
    its reading of the value from the key's first lanes and its layer
    offset, under the TPU interpreter (which runs a DMA only when it is
    waited on and fills fresh scratch with NaN), against the jnp
    absorbed attention over the gathered pages.  bf16 inputs: both
    sides multiply the same bf16 values into f32 sums; the outputs are
    rounded to bf16 once, hence 2 bf16 ulps."""
    G, P, page, lanes, r, H, B, n = 2, 24, 16, 128, 64, 4, 4, 20
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    pool = jax.random.normal(k1, (G, P, page, lanes)).astype(dtype)
    q = jax.random.normal(k2, (B, H, lanes)).astype(dtype)
    table = jax.random.randint(k3, (B, n), 1, P, jnp.int32)
    lens = jnp.asarray([n * page, 257, 17, 0], jnp.int32)
    layer, scale = 1, 0.2
    got = paged_decode_attention_latent_pallas(
        q, pool.reshape(G * P, page, lanes), table + layer * P, lens,
        v_lanes=r, scale=scale, interpret=pltpu.InterpretParams())
    seat = pool[layer][table].reshape(B, n * page, lanes)
    s = jnp.einsum("bhl,btl->bht", q.astype(jnp.float32),
                   seat.astype(jnp.float32)) * scale
    s = jnp.where(jnp.arange(n * page)[None, None] < lens[:, None, None], s,
                  -jnp.inf)
    want = jnp.einsum("bht,btc->bhc", jax.nn.softmax(s, -1),
                      seat[..., :r].astype(jnp.float32))
    live = np.asarray(lens) > 0
    tol = 1e-5 if dtype == jnp.float32 else 2 * 2.0 ** -8
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want)[live],
        rtol=tol, atol=tol)


def _published_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """transformers' _compute_yarn_parameters, written out."""
    pos_freqs = base ** (np.arange(0, dim, 2) / dim)
    extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra_factor = 1 - ramp
    return inter * (1 - extra_factor) + extra * extra_factor


def test_yarn_frequencies_and_softmax_scale():
    cfg = get_config("deepseek-v2-lite")
    inv, attn = rope_frequencies(64, cfg.rope_theta, cfg.rope_scaling)
    want = _published_yarn(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    # float32 storage of float64 formulas: one f32 rounding
    np.testing.assert_allclose(inv, want, rtol=1e-7)
    assert inv[0] == pytest.approx(1.0) and inv[-1] < want[0] / 40 * 1.01
    assert attn == 1.0          # mscale / mscale_all_dim, both 0.707
    # DeepSeek's softmax_scale: qk_head_dim^-0.5 * yarn_get_mscale(40,
    # 0.707)^2, with yarn_get_mscale(s, m) = 0.1 m ln s + 1
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                   rel=1e-12)
    assert 1.589 < m * m < 1.590


def test_tick_names_the_latent_and_expert_scopes():
    """The fused tick's HLO carries the named scopes a profiler trace is
    read by (op metadata, the ``tf_op`` stat on a TPU): the latent
    kernel call inside ``paged_attention``, the query absorption inside
    ``attn_proj``, the expert layer's three parts inside ``mlp``."""
    from repro.models import model as M
    from repro.parallel.sharding import SINGLE_DEVICE_RULES
    cfg = dataclasses.replace(
        _cfg(), moe=dataclasses.replace(_cfg().moe, experts_held=2))
    params = M.init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    cache = M.init_paged_cache(cfg, 8, 16)
    A, n = 2, 4
    i32, u32 = jnp.zeros((A,), jnp.int32), jnp.zeros((A,), jnp.uint32)
    opts = M.RunOptions(paged_attn_impl="pallas")
    text = jax.jit(lambda c: M.fused_decode_tick(
        params, cfg, c, i32, i32 + 3, jnp.ones((A, n), jnp.int32), i32 + 1,
        jnp.zeros((A,), jnp.float32), i32, jnp.ones((A,), jnp.float32),
        u32, u32, u32, SINGLE_DEVICE_RULES, opts)).lower(cache).as_text(
            debug_info=True)
    for stack in ("paged_attention/latent_attention", "attn_proj/mla_absorb",
                  "mlp/moe_route", "mlp/moe_experts", "mlp/moe_shared"):
        assert stack in text, stack
