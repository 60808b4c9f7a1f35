"""The plain reference against the program at a tiny size, on the CPU:
chunked paged prefill and then decode through the paged cache must give
the reference's full-forward logits, through a tied or an untied head;
and the weights a run draws from its seed, in the reference's layout."""
import bench_tiny  # noqa: F401  (paths)

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from repro.models import model as M
from repro.parallel.sharding import SINGLE_DEVICE_RULES

R = bench_tiny.REF

PROMPT, DECODE, PAGE, PAGES = 40, 6, 16, 12


def _ref_logits(params, m, seq, fp8=False):
    """Every position's logits: the reference's hidden states through
    the head the layout holds (``lm_head`` (D, V) where untied)."""
    h = R.hidden(params, jnp.asarray(seq)[None], m, fp8=fp8)[0]
    head = (params["lm_head"] if "lm_head" in params
            else params["embed"].T).astype(jnp.float32)
    return np.asarray(jnp.einsum("td,dv->tv", h, head,
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
    params = bench_tiny.params(m, 3, jnp.float32)
    seq = np.random.default_rng(0).integers(0, m["vocab_size"],
                                            PROMPT + DECODE).astype(np.int32)
    got = _program_logits(params, m, arch, seq, impl)
    want = _ref_logits(params, m, seq)
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err


def test_control_departs_from_reference():
    m = bench_tiny.model()
    params = bench_tiny.params(m, 5, jnp.float32)
    seq = np.random.default_rng(1).integers(0, 256, 64).astype(np.int32)
    ref = _ref_logits(params, m, seq)
    ctl = _ref_logits(params, m, seq, fp8=True)
    rel = np.linalg.norm(ctl - ref) / np.linalg.norm(ref)
    assert 1e-3 < rel < 0.5, rel


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_untied_head_matches_reference(impl):
    """An untied head (``lm_head`` (D, V), its own leaf): the program's
    logits against the reference's, and the reference's head functions
    against those logits."""
    m = bench_tiny.model(tie_embeddings=False, compute_dtype="float32")
    params = bench_tiny.params(m, 4, jnp.float32)
    assert params["lm_head"].shape == (m["d_model"], m["vocab_size"])
    seq = np.random.default_rng(2).integers(0, m["vocab_size"],
                                            PROMPT + DECODE).astype(np.int32)
    got = _program_logits(params, m, "qwen3-1.7b", seq, impl)
    want = _ref_logits(params, m, seq)
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err
    # head_stats and head_argmax_fp8 pick lm_head, not the embedding
    h = R.hidden(params, jnp.asarray(seq)[None], m, fp8=False)[0]
    rows = jnp.zeros((R.HEAD_BLOCK, m["d_model"])).at[:len(seq)].set(h)
    pick = np.zeros(R.HEAD_BLOCK, np.int32)
    pick[:len(seq)] = got.argmax(-1)
    best, tgt = R.head_stats(params, rows, jnp.asarray(pick))
    np.testing.assert_allclose(np.asarray(best)[:len(seq)], want.max(-1),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(best - tgt)[:len(seq)]).max() < 1e-4
    ctl = np.asarray(R.head_argmax_fp8(params, rows))[:len(seq)]
    assert (ctl == want.argmax(-1)).mean() > 0.5


# sha256 (first 16 hex digits) of each leaf's bfloat16 bytes, as the
# weights were drawn before the layout moved into the reference module
# (tiny model, seed 2**31 + 9): the move must leave every draw as it was
PINNED = {
    True: {
        "blocks/pos0/ln1": "92fc9e6b0027d958",
        "blocks/pos0/ln2": "572f39fad7fb2fe7",
        "blocks/pos0/mixer/k_norm": "2da010ac6007de80",
        "blocks/pos0/mixer/q_norm": "3dafa6682c51c326",
        "blocks/pos0/mixer/wk": "153fa7502c409605",
        "blocks/pos0/mixer/wo": "b50a62b9579da66a",
        "blocks/pos0/mixer/wq": "57fa2b4a0935980a",
        "blocks/pos0/mixer/wv": "80c1b5b8bb442b20",
        "blocks/pos0/mlp/wd": "5bb54d49d204f323",
        "blocks/pos0/mlp/wg": "1744bfc9344aa7b2",
        "blocks/pos0/mlp/wu": "34afc067a82b2940",
        "embed": "43c802dafc2d107f",
        "final_norm": "40d678202eedefdf",
    },
    False: {
        "blocks/pos0/ln1": "92fc9e6b0027d958",
        "blocks/pos0/ln2": "572f39fad7fb2fe7",
        "blocks/pos0/mixer/wk": "42b4fb32b0e39a7b",
        "blocks/pos0/mixer/wo": "24df6ff0d3653272",
        "blocks/pos0/mixer/wq": "2c4160269a3710df",
        "blocks/pos0/mixer/wv": "b50a62b9579da66a",
        "blocks/pos0/mlp/wd": "c62e2fec0c58de28",
        "blocks/pos0/mlp/wg": "3c9a38299d71b3e3",
        "blocks/pos0/mlp/wu": "a17bc9bfd56098f1",
        "embed": "1744bfc9344aa7b2",
        "final_norm": "2813f20d4fa6224e",
    },
}


@pytest.mark.parametrize("qk_norm", [True, False])
def test_weights_draw_as_pinned(qk_norm):
    m = bench_tiny.model(qk_norm=qk_norm, kv_heads=2 if qk_norm else 4)
    p = bench_tiny.params(m, 2 ** 31 + 9, "bfloat16")
    got = {"/".join(k.key for k in path):
           hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
           for path, x in jax.tree_util.tree_flatten_with_path(p)[0]}
    assert got == PINNED[qk_norm]


def test_weights_follow_the_seed_and_the_program_layout():
    for tie in (True, False):
        m = bench_tiny.model(tie_embeddings=tie)
        a = bench_tiny.params(m, 2 ** 31 + 9, jnp.bfloat16)
        b = bench_tiny.params(m, 2 ** 31 + 9, jnp.bfloat16)
        c = bench_tiny.params(m, 2 ** 31 + 10, jnp.bfloat16)
        la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
        assert all(x.dtype == jnp.bfloat16 for x in la)
        assert all(np.array_equal(x, y) for x, y in zip(la, lb))
        assert not np.array_equal(la[0], lc[0])
        assert ("lm_head" in a) is not tie
        cfg = harness.program_config(bench_tiny.config(m))
        want = jax.tree.map(lambda s: s.shape, M.abstract_params(
            M.param_specs(cfg)))
        assert jax.tree.map(lambda x: x.shape, a) == want
