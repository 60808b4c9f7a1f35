"""The plain reference against the program at a tiny size, on the CPU:
chunked paged prefill and then decode through the paged cache must give
the reference's full-forward logits."""
import bench_tiny  # noqa: F401  (paths)

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import catalog
import harness
import weights as W
from repro.models import model as M
from repro.parallel.sharding import SINGLE_DEVICE_RULES

R = catalog.load_module(f"{bench_tiny.BENCH}/reference/dense_decoder.py",
                        "bench_reference_dense_decoder")

PROMPT, DECODE, PAGE, PAGES = 40, 6, 16, 12


def _ref_logits(params, m, seq, fp8=False):
    h = R.hidden(params, jnp.asarray(seq)[None], eps=m["norm_eps"],
                 theta=m["rope_theta"], fp8=fp8)[0]
    return np.asarray(jnp.einsum("td,vd->tv", h,
                                 params["embed"].astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST))


def _program_logits(params, m, arch, seq, impl):
    """Prefill seq[:PROMPT] in chunks of 16 through the paged step,
    then decode the rest one token at a time: logits at every
    position."""
    cfg = harness.program_config(bench_tiny.config(m, arch))
    opts = M.RunOptions(paged_attn_impl=impl)
    cache = M.init_paged_cache(cfg, PAGES, PAGE)
    n = -(-(PROMPT + DECODE) // PAGE)
    table = jnp.arange(1, 1 + n, dtype=jnp.int32)[None]
    step = jax.jit(lambda c, t, q, nv: M.paged_decode_step(
        params, cfg, c, t, q, table, nv, SINGLE_DEVICE_RULES, opts))
    out = []
    for s in range(0, PROMPT, 16):
        chunk = np.zeros((1, 16), np.int32)
        c = min(16, PROMPT - s)
        chunk[0, :c] = seq[s:s + c]
        lg, cache = step(cache, jnp.asarray(chunk), jnp.asarray([s]),
                         jnp.asarray([c]))
        out.append(np.asarray(lg[0, :c]))
    for p in range(PROMPT, PROMPT + DECODE):
        lg, cache = step(cache, jnp.asarray([[seq[p]]], jnp.int32),
                         jnp.asarray([p]), jnp.asarray([1]))
        out.append(np.asarray(lg[0]))
    return np.concatenate(out)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("arch,qk_norm,kv_heads",
                         [("qwen3-1.7b", True, 2), ("minicpm-2b", False, 4)])
def test_paged_prefill_and_decode_match_reference(arch, qk_norm, kv_heads,
                                                  impl):
    # float32 on both sides: what is compared is the maths (rope
    # halves, qk-norm, GQA head mapping, norm offsets, tied head), so
    # the two orders of summation may differ by rounding only
    m = bench_tiny.model(qk_norm=qk_norm, kv_heads=kv_heads,
                         compute_dtype="float32")
    params = W.make(m, 3, jnp.float32)
    seq = np.random.default_rng(0).integers(0, m["vocab_size"],
                                            PROMPT + DECODE).astype(np.int32)
    got = _program_logits(params, m, arch, seq, impl)
    want = _ref_logits(params, m, seq)
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err


def test_control_departs_from_reference():
    m = bench_tiny.model()
    params = W.make(m, 5, jnp.float32)
    seq = np.random.default_rng(1).integers(0, 256, 64).astype(np.int32)
    ref = _ref_logits(params, m, seq)
    ctl = _ref_logits(params, m, seq, fp8=True)
    rel = np.linalg.norm(ctl - ref) / np.linalg.norm(ref)
    assert 1e-3 < rel < 0.5, rel


def test_weights_follow_the_seed_and_the_program_layout():
    m = bench_tiny.model()
    a = W.make(m, 2 ** 31 + 9, jnp.bfloat16)
    b = W.make(m, 2 ** 31 + 9, jnp.bfloat16)
    c = W.make(m, 2 ** 31 + 10, jnp.bfloat16)
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(x.dtype == jnp.bfloat16 for x in la)
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[0], lc[0])
    cfg = harness.program_config(bench_tiny.config(m))
    want = jax.tree.map(lambda s: s.shape, M.abstract_params(
        M.param_specs(cfg)))
    assert jax.tree.map(lambda x: x.shape, a) == want
