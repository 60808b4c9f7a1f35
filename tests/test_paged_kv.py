"""Paged KV serving subsystem: block-manager invariants, the paged
decode-attention kernel vs its references, and token-exact equivalence of
the paged engine against the dense fixed-slot engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config, reduced_config
from repro.kernels import ops
from repro.kernels.decode_attention import paged_decode_attention_pallas
from repro.kernels.ref import decode_attention_ref, paged_decode_attention_ref
from repro.models import model as M
from repro.runtime.paged_kv import BlockManager
from repro.runtime.serving import PagedServingEngine, ServingEngine


# -- block manager -----------------------------------------------------------

def test_block_manager_no_page_shared_and_scratch_reserved():
    bm = BlockManager(num_pages=8, page_size=16)
    a = bm.alloc(3, rid=0)
    b = bm.alloc(4, rid=1)
    assert a is not None and b is not None
    assert 0 not in a + b                       # scratch page never allocated
    assert len(set(a) | set(b)) == 7            # disjoint ownership
    assert bm.owner(a[0]) == 0 and bm.owner(b[0]) == 1
    assert bm.available == 0


def test_block_manager_alloc_failure_returns_none():
    bm = BlockManager(num_pages=4, page_size=16)
    assert bm.alloc(4, rid=0) is None           # only 3 usable pages
    assert bm.available == 3                    # failed alloc takes nothing
    got = bm.alloc(3, rid=0)
    assert got is not None and bm.alloc(1, rid=1) is None


def test_block_manager_free_cycle_and_double_free():
    bm = BlockManager(num_pages=6, page_size=8)
    pages = bm.alloc(5, rid=7)
    bm.free(pages)
    assert bm.available == bm.capacity == 5
    with pytest.raises(ValueError):
        bm.free(pages[:1])                      # double free
    assert bm.peak_in_use == 5


def test_pages_needed_rounding():
    bm = BlockManager(num_pages=8, page_size=16)
    assert bm.pages_needed(1) == 1
    assert bm.pages_needed(16) == 1
    assert bm.pages_needed(17) == 2


# -- kernel vs references ----------------------------------------------------

def _paged_problem(rng, B, KVH, rep, d, P, page, n, lengths, dtype=jnp.float32,
                   shared=False):
    """Random q and pools in the kernel's layout and a page table that maps
    each seat's live pages to shuffled physical pages (dead entries name
    the scratch page 0).  ``shared``: seats 0 and 1 map their first
    logical page to one physical page."""
    q = jnp.asarray(rng.normal(size=(B, KVH, rep, d)), dtype)
    kp = jnp.asarray(rng.normal(size=(KVH, P, page, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(KVH, P, page, d)), dtype)
    pt = np.zeros((B, n), np.int32)
    avail = list(rng.permutation(np.arange(1, P)))
    for b in range(B):
        for i in range(-(-int(lengths[b]) // page)):
            pt[b, i] = avail.pop()
    if shared:
        pt[1, 0] = pt[0, 0]
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lengths, jnp.int32)


# The TPU interpreter runs the kernel's DMAs only when they are waited on,
# and fills fresh scratch with NaN: a missing wait or a stale buffer shows
TPU_INTERPRET = pltpu.InterpretParams()

# (B, KVH, rep, d, P, page, n, lengths, shared): 256-token blocks, so with
# 64-token pages a block is 4 pages: an 8-page table holds 2 blocks and a
# 3-page one is padded to one.  Heads of 64 and 32 are packed 2 and 4
# tokens to a 128-lane row; heads of 96 are padded to 128 lanes.
PAGED_CASES = {
    "mha_d64_len1_page_and_block_edges": (4, 3, 1, 64, 40, 64, 8,
                                          [1, 64, 256, 257], False),
    "gqa_d128_full_table": (3, 2, 2, 128, 32, 64, 8, [512, 300, 65], False),
    "table_not_a_block_multiple": (3, 2, 2, 128, 32, 64, 3,
                                   [192, 129, 64], False),
    "shared_physical_page": (3, 2, 2, 64, 24, 64, 4, [100, 200, 17], True),
    "small_pages_f32": (6, 1, 1, 32, 16, 8, 4, [1, 8, 9, 17, 31, 32], False),
    "head_padded_to_lanes": (3, 2, 2, 96, 24, 64, 4, [256, 100, 1], False),
}


@pytest.mark.parametrize("case", list(PAGED_CASES))
def test_paged_kernel_matches_refs(case):
    B, KVH, rep, d, P, page, n, lengths, shared = PAGED_CASES[case]
    rng = np.random.default_rng(0)
    q, kp, vp, pt, lens = _paged_problem(rng, B, KVH, rep, d, P, page, n,
                                         lengths, shared=shared)
    out = paged_decode_attention_pallas(q, kp, vp, pt, lens,
                                        interpret=TPU_INTERPRET)
    ref = paged_decode_attention_ref(q, kp, vp, pt, lens)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    # the paged ref itself must equal dense decode on the gathered cache
    for h in range(KVH):
        k = np.asarray(kp)[h][np.asarray(pt)].reshape(B, -1, d)
        v = np.asarray(vp)[h][np.asarray(pt)].reshape(B, -1, d)
        for r in range(rep):
            dense = decode_attention_ref(q[:, h, r], jnp.asarray(k),
                                         jnp.asarray(v), lens)
            np.testing.assert_allclose(np.asarray(ref[:, h, r]),
                                       np.asarray(dense), atol=1e-6)


def test_paged_kernel_bf16_pool_within_one_rounding():
    """bf16 q and pool go to the MXU unconverted: the output differs from
    the f32 oracle on the same bf16 values by at most the bf16 rounding of
    the result."""
    rng = np.random.default_rng(3)
    q, kp, vp, pt, lens = _paged_problem(rng, 3, 2, 2, 128, 32, 64, 8,
                                         [512, 257, 1], dtype=jnp.bfloat16)
    out = paged_decode_attention_pallas(q, kp, vp, pt, lens,
                                        interpret=TPU_INTERPRET)
    ref = paged_decode_attention_ref(q.astype(jnp.float32),
                                     kp.astype(jnp.float32),
                                     vp.astype(jnp.float32), pt, lens)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=1e-2, rtol=1e-2)


@pytest.mark.parametrize("H,KVH,d", [(4, 2, 16), (3, 3, 64)])
def test_paged_ops_wrapper_gqa_expansion(H, KVH, d):
    rng = np.random.default_rng(1)
    B, P, page, n = 3, 12, 8, 3
    q = jnp.asarray(rng.normal(size=(B, 1, H, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, KVH, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, KVH, d)), jnp.float32)
    pt = np.zeros((B, n), np.int32)
    lengths = rng.integers(1, n * page, size=(B,)).astype(np.int32)
    avail = list(range(1, P))
    for b in range(B):
        for i in range(-(-int(lengths[b]) // page)):
            pt[b, i] = avail.pop()
    out = ops.paged_decode_attention(q, kp, vp, jnp.asarray(pt),
                                     jnp.asarray(lengths))
    rep = H // KVH
    for h in range(H):
        kk = np.asarray(kp)[:, :, h // rep][pt].reshape(B, -1, d)
        vv = np.asarray(vp)[:, :, h // rep][pt].reshape(B, -1, d)
        ref = decode_attention_ref(q[:, 0, h], jnp.asarray(kk),
                                   jnp.asarray(vv), jnp.asarray(lengths))
        np.testing.assert_allclose(np.asarray(out[:, 0, h]), np.asarray(ref),
                                   atol=2e-6)


# -- engine equivalence ------------------------------------------------------

@pytest.fixture(scope="module")
def engine_setup():
    cfg = reduced_config(get_config("qwen3-1.7b"))
    params = M.init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def test_paged_matches_fixed_slot_tokens(engine_setup):
    """Paged decode (through chunked prefill + page-table gather) must be
    token-identical to the dense fixed-slot engine on the same request."""
    cfg, params = engine_setup
    prompt = np.arange(7, dtype=np.int32) % cfg.vocab_size
    fixed = ServingEngine(cfg, params, slots=2, max_len=32)
    fixed.submit(prompt, max_new_tokens=5)
    want = fixed.run()[0].generated

    eng = PagedServingEngine(cfg, params, page_size=8, num_pages=16,
                             max_seats=2, max_seq_len=32, prefill_chunk=4)
    eng.submit(prompt, max_new_tokens=5)
    done = eng.run()
    assert len(done) == 1
    assert done[0].generated == want


def test_paged_random_prompts_match_fixed(engine_setup):
    """Token-exact equivalence on a batch of random prompts served
    concurrently (mixed lengths, seat contention)."""
    cfg, params = engine_setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in rng.integers(3, 20, size=5)]
    gens = [int(g) for g in rng.integers(2, 7, size=5)]

    want = {}
    for i, (p, g) in enumerate(zip(prompts, gens)):
        solo = ServingEngine(cfg, params, slots=1, max_len=32)
        solo.submit(p, max_new_tokens=g)
        want[i] = solo.run()[0].generated

    eng = PagedServingEngine(cfg, params, page_size=8, num_pages=24,
                             max_seats=3, max_seq_len=32, prefill_chunk=8)
    rid_to_i = {eng.submit(p, max_new_tokens=g): i
                for i, (p, g) in enumerate(zip(prompts, gens))}
    done = eng.run()
    assert len(done) == 5
    for r in done:
        assert r.generated == want[rid_to_i[r.rid]], r.rid


def test_engine_pallas_impl_matches_jnp(engine_setup):
    """The kernel decode path (interpret mode on CPU) produces the same
    greedy tokens as the jnp gather path through the full engine."""
    cfg, params = engine_setup
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab_size
    outs = {}
    for impl in ("jnp", "pallas"):
        eng = PagedServingEngine(
            cfg, params, page_size=8, num_pages=8, max_seats=1,
            max_seq_len=16, prefill_chunk=8,
            opts=M.RunOptions(q_chunk=16, paged_attn_impl=impl))
        eng.submit(prompt, max_new_tokens=3)
        outs[impl] = eng.run()[0].generated
    assert outs["pallas"] == outs["jnp"]


def test_no_page_shared_across_live_requests(engine_setup):
    """While requests are in flight, page-table rows of distinct seats
    never name the same physical page (and never the scratch page)."""
    cfg, params = engine_setup
    eng = PagedServingEngine(cfg, params, page_size=8, num_pages=12,
                             max_seats=3, max_seq_len=32, prefill_chunk=8)
    rng = np.random.default_rng(5)
    for i in range(6):
        eng.submit(rng.integers(0, cfg.vocab_size, 5 + i).astype(np.int32),
                   max_new_tokens=3)
    saw_live = False
    while eng.queue or eng.seats:
        eng.step()
        live = [pg for r in eng.seats.values() for pg in r.pages]
        assert 0 not in live
        assert len(live) == len(set(live)), "page shared across requests"
        saw_live = saw_live or len(eng.seats) > 1
    assert saw_live                       # the assertion above actually bit


def test_pages_freed_on_completion_and_queueing_not_crashing(engine_setup):
    """A pool too small for the whole workload queues requests (no crash),
    serves everyone eventually, and ends with every page back in the pool."""
    cfg, params = engine_setup
    eng = PagedServingEngine(cfg, params, page_size=8, num_pages=7,
                             max_seats=4, max_seq_len=32, prefill_chunk=8)
    rng = np.random.default_rng(7)
    rids = [eng.submit(rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                       max_new_tokens=4) for _ in range(5)]
    # 5 requests x 2 pages each > 6 usable pages: someone must wait
    waited = False
    while eng.queue or eng.seats:
        eng.step()
        waited = waited or (len(eng.queue) > 0 and len(eng.seats) > 0)
    assert waited
    assert sorted(r.rid for r in eng.finished) == sorted(rids)
    assert eng.bm.in_use == 0
    assert eng.bm.available == eng.bm.capacity
    assert np.all(eng.page_table == 0)


def test_oversized_request_rejected(engine_setup):
    cfg, params = engine_setup
    # up-front reservation: per-request max_seq_len bound AND pool check
    eng = PagedServingEngine(cfg, params, page_size=8, num_pages=4,
                             max_seats=2, max_seq_len=40, lazy_pages=False)
    with pytest.raises(ValueError):
        eng.submit(np.arange(44, dtype=np.int32), max_new_tokens=4)  # > max_seq_len
    with pytest.raises(ValueError):
        eng.submit(np.arange(28, dtype=np.int32), max_new_tokens=4)  # > pool
    # lazy growth: max_seq_len is the only per-request bound — a pool too
    # small to cover one max-length request is rejected at construction
    with pytest.raises(ValueError):
        PagedServingEngine(cfg, params, page_size=8, num_pages=4,
                           max_seats=2, max_seq_len=40)
    lazy = PagedServingEngine(cfg, params, page_size=8, num_pages=6,
                              max_seats=2, max_seq_len=40)
    with pytest.raises(ValueError):
        lazy.submit(np.arange(44, dtype=np.int32), max_new_tokens=4)
    with pytest.raises(ValueError):
        PagedServingEngine(reduced_config(get_config("mamba2-130m")),
                           params, page_size=8, num_pages=4)  # ssm: unsupported
