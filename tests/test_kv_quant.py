"""Quantized paged KV (fp8/int8 pages with per-(token, head) scales):
kernel-vs-oracle tolerance, cache layout and byte accounting, exactness
of CoW / preemption replay within a precision, per-class precision
floors, and byte-denominated fleet budgeting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config, reduced_config
from repro.core import mixed_precision as mp
from repro.kernels import ops
from repro.kernels.decode_attention import (
    paged_decode_attention_pallas, quantized_paged_decode_attention_pallas)
from repro.kernels.ref import (decode_attention_ref,
                               paged_decode_attention_ref,
                               quantized_paged_decode_attention_ref)
from repro.models import model as M
from repro.runtime.paged_kv import BlockManager
from repro.runtime.router import FleetModel, HostBudget, ModelFleet
from repro.runtime.serving import PagedServingEngine


# -- quantization helpers -----------------------------------------------------

@pytest.mark.parametrize("kv_dtype", mp.KV_QUANTIZED)
def test_quantize_kv_page_shapes_and_dtypes(kv_dtype):
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 8, 3, 16)),
                    jnp.float32)
    q, s = mp.quantize_kv_page(x, kv_dtype)
    assert q.shape == x.shape and s.shape == x.shape[:-1]
    assert q.dtype == mp.kv_storage_dtype(kv_dtype)
    assert s.dtype == jnp.float32
    back = mp.dequantize_kv_page(q, s)
    assert back.shape == x.shape and back.dtype == jnp.float32


def test_quantize_kv_page_rejects_unquantized_dtypes():
    x = jnp.ones((2, 4))
    for dt in ("f32", "bf16"):
        with pytest.raises(ValueError, match="quantized"):
            mp.quantize_kv_page(x, dt)
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        mp.quantize_kv_page(x, "fp4")


def test_quantize_kv_page_write_order_independence():
    """A vector's quantized bytes depend only on its own values — the
    invariant CoW and preemption replay lean on."""
    rng = np.random.default_rng(3)
    page = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    for dt in mp.KV_QUANTIZED:
        q_full, s_full = mp.quantize_kv_page(page, dt)
        q_row, s_row = mp.quantize_kv_page(page[3], dt)
        np.testing.assert_array_equal(
            np.asarray(q_full[3]).view(np.uint8),
            np.asarray(q_row).view(np.uint8))
        np.testing.assert_array_equal(np.asarray(s_full[3]),
                                      np.asarray(s_row))


def test_kv_token_bytes_and_precision_bits():
    assert mp.kv_token_bytes("f32", 64) == 256
    assert mp.kv_token_bytes("bf16", 64) == 128
    assert mp.kv_token_bytes("fp8", 64) == 64 + 4      # values + f32 scale
    assert mp.kv_token_bytes("int8", 64) == 64 + 4
    bits = [mp.kv_precision_bits(d) for d in ("f32", "bf16", "fp8", "int8")]
    assert bits == [32, 16, 8, 8]
    with pytest.raises(ValueError):
        mp.kv_precision_bits("fp4")


# -- quantized kernel vs references -------------------------------------------

def _paged_problem(seed, B=3, KVH=2, rep=2, d=32, P=16, page=8, n=4):
    """q (B, KVH, rep, d) and pools (KVH, P, page, d) in the kernel's
    layout, with each seat's live pages on distinct physical pages."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, KVH, rep, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(KVH, P, page, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(KVH, P, page, d)), jnp.float32)
    pt = np.zeros((B, n), np.int32)
    lengths = rng.integers(1, n * page, size=(B,)).astype(np.int32)
    avail = list(range(1, P))
    for b in range(B):
        for i in range(-(-int(lengths[b]) // page)):
            pt[b, i] = avail.pop()
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lengths)


@pytest.mark.parametrize("kv_dtype", mp.KV_QUANTIZED)
def test_quantized_kernel_matches_oracle(kv_dtype):
    """The Pallas kernel dequantizing in VMEM must match the jnp oracle
    that dequantizes the whole pool first — same math, tight tolerance."""
    q, kp, vp, pt, lengths = _paged_problem(0)
    kq, ks = mp.quantize_kv_page(kp, kv_dtype)
    vq, vs = mp.quantize_kv_page(vp, kv_dtype)
    out = quantized_paged_decode_attention_pallas(
        q, kq, vq, ks, vs, pt, lengths, interpret=pltpu.InterpretParams())
    want = quantized_paged_decode_attention_ref(q, kq, vq, ks, vs, pt,
                                                lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=5e-6)


@pytest.mark.parametrize("kv_dtype", mp.KV_QUANTIZED)
def test_quantized_kernel_close_to_full_precision(kv_dtype):
    """Quantized attention output stays within the storage format's
    error envelope of the full-precision kernel (~2^-4 relative for
    e4m3's 3 mantissa bits; int8 is finer)."""
    q, kp, vp, pt, lengths = _paged_problem(1)
    kq, ks = mp.quantize_kv_page(kp, kv_dtype)
    vq, vs = mp.quantize_kv_page(vp, kv_dtype)
    out = quantized_paged_decode_attention_pallas(
        q, kq, vq, ks, vs, pt, lengths, interpret=pltpu.InterpretParams())
    full = paged_decode_attention_pallas(q, kp, vp, pt, lengths,
                                         interpret=pltpu.InterpretParams())
    # outputs are convex combinations of unit-scale v rows: abs error
    # bounded by the per-element quantization error plus softmax shift
    tol = 0.25 if kv_dtype == "fp8" else 0.08
    assert float(jnp.max(jnp.abs(out - full))) < tol
    # and the quantized ref equals dense decode on the dequantized pool
    kd = mp.dequantize_kv_page(kq, ks)
    vd = mp.dequantize_kv_page(vq, vs)
    dense = paged_decode_attention_ref(q, kd, vd, pt, lengths)
    want = quantized_paged_decode_attention_ref(q, kq, vq, ks, vs, pt,
                                                lengths)
    np.testing.assert_allclose(np.asarray(want), np.asarray(dense),
                               atol=1e-6)


def test_quantized_ops_wrapper_gqa_expansion():
    rng = np.random.default_rng(2)
    B, H, KVH, d, P, page, n = 3, 4, 2, 16, 12, 8, 3
    q = jnp.asarray(rng.normal(size=(B, 1, H, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(P, page, KVH, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, page, KVH, d)), jnp.float32)
    pt = np.zeros((B, n), np.int32)
    lengths = rng.integers(1, n * page, size=(B,)).astype(np.int32)
    avail = list(range(1, P))
    for b in range(B):
        for i in range(-(-int(lengths[b]) // page)):
            pt[b, i] = avail.pop()
    kq, ks = mp.quantize_kv_page(kp, "fp8")
    vq, vs = mp.quantize_kv_page(vp, "fp8")
    out = ops.paged_decode_attention(q, kq, vq, jnp.asarray(pt),
                                     jnp.asarray(lengths),
                                     k_scale=ks, v_scale=vs)
    kd, vd = mp.dequantize_kv_page(kq, ks), mp.dequantize_kv_page(vq, vs)
    rep = H // KVH
    for h in range(H):
        kk = np.asarray(kd)[:, :, h // rep][pt].reshape(B, -1, d)
        vv = np.asarray(vd)[:, :, h // rep][pt].reshape(B, -1, d)
        ref = decode_attention_ref(q[:, 0, h], jnp.asarray(kk),
                                   jnp.asarray(vv), jnp.asarray(lengths))
        np.testing.assert_allclose(np.asarray(out[:, 0, h]),
                                   np.asarray(ref), atol=2e-5)


def test_ops_wrapper_requires_scale_pair():
    q, kp, vp, pt, lengths = _paged_problem(4)
    B, KVH, rep, d = q.shape
    kq, ks = mp.quantize_kv_page(kp, "fp8")
    with pytest.raises(ValueError, match="together"):
        ops.paged_decode_attention(q.reshape(B, 1, KVH * rep, d),
                                   kq.transpose(1, 2, 0, 3),
                                   vp.transpose(1, 2, 0, 3), pt, lengths,
                                   k_scale=ks.transpose(1, 2, 0))


# -- cache layout and byte accounting -----------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config(get_config("qwen3-1.7b"))
    params = M.init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def test_init_paged_cache_layouts(setup):
    cfg, _ = setup
    plain = M.init_paged_cache(cfg, 8, 4)
    ent = plain["pos0"]
    assert set(ent) == {"k", "v"}               # pre-quantization layout
    assert ent["k"].dtype == jnp.dtype(cfg.compute_dtype)
    for dt in mp.KV_QUANTIZED:
        c = M.init_paged_cache(cfg, 8, 4, kv_dtype=dt)
        e = c["pos0"]
        assert set(e) == {"k", "v", "ks", "vs"}
        assert e["k"].dtype == mp.kv_storage_dtype(dt)
        assert e["ks"].dtype == jnp.float32
        assert e["ks"].shape == e["k"].shape[:-1]
    # f32/bf16 as explicit kv_dtype: plain layout at that precision
    c = M.init_paged_cache(cfg, 8, 4, kv_dtype="f32")
    assert set(c["pos0"]) == {"k", "v"}
    assert c["pos0"]["k"].dtype == jnp.float32


def test_paged_page_bytes_arithmetic(setup):
    cfg, _ = setup
    kvh, hd, L = cfg.padded_kv_heads, cfg.resolved_head_dim, cfg.num_layers
    page = 4
    assert (M.paged_page_bytes(cfg, page) ==
            L * page * kvh * hd * jnp.dtype(cfg.compute_dtype).itemsize * 2)
    assert (M.paged_page_bytes(cfg, page, "fp8") ==
            L * page * kvh * (hd + 4) * 2)
    assert (M.paged_page_bytes(cfg, page, "f32") ==
            L * page * kvh * hd * 4 * 2)
    # the effective-capacity win: fp8 pages cost under half of f32 ones
    assert (M.paged_page_bytes(cfg, page, "fp8") * 2 <
            M.paged_page_bytes(cfg, page, "f32"))


def test_engine_metrics_expose_kv_bytes(setup):
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, page_size=4, num_pages=12,
                             max_seats=2, max_seq_len=20, prefill_chunk=8,
                             kv_dtype="fp8")
    assert eng.kv_dtype == "fp8"
    eng.submit(np.arange(6, dtype=np.int32) % cfg.vocab_size,
               max_new_tokens=3)
    eng.run()
    snap = eng.metrics.snapshot()
    assert snap["kv_dtype"] == "fp8"
    assert snap["page_bytes"] == M.paged_page_bytes(cfg, 4, "fp8")
    assert snap["kv_bytes_in_use"] == 0         # drained pool
    assert eng.policy.bm.page_bytes == snap["page_bytes"]


# -- exactness within a precision ---------------------------------------------

@pytest.mark.parametrize("kv_dtype", mp.KV_QUANTIZED)
def test_quantized_prefix_cache_token_identical_on_vs_off(setup, kv_dtype):
    """CoW over quantized pages: heavy prefix overlap generates the same
    tokens with sharing on and off — per-(token, head) scales make the
    stored bytes write-order independent."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    base = rng.integers(0, cfg.vocab_size, 11).astype(np.int32)
    reqs = [(base, 5), (base.copy(), 5),
            (np.concatenate([base[:6],
                             rng.integers(0, cfg.vocab_size,
                                          3).astype(np.int32)]), 4)]
    kw = dict(page_size=4, num_pages=24, max_seats=3, max_seq_len=24,
              prefill_chunk=4, kv_dtype=kv_dtype)

    def run(prefix_cache):
        eng = PagedServingEngine(cfg, params, prefix_cache=prefix_cache,
                                 **kw)
        for p, g in reqs:
            eng.submit(p, max_new_tokens=g)
            for _ in range(3):
                eng.step()
        return eng, {r.rid: r.generated for r in eng.run()}

    eng_on, on = run(True)
    _, off = run(False)
    assert on == off
    assert eng_on.metrics.snapshot()["cached_prompt_tokens"] > 0


@pytest.mark.parametrize("kv_dtype", mp.KV_QUANTIZED)
def test_quantized_preemption_replay_token_identical(setup, kv_dtype):
    """Preempt-and-recompute on a quantized pool replays to the same
    token stream as an uncontended run at the same precision."""
    cfg, params = setup
    reqs = [((np.arange(8, dtype=np.int32) * 3) % cfg.vocab_size, 10),
            ((np.arange(8, dtype=np.int32) * 7) % cfg.vocab_size, 10)]
    kw = dict(page_size=4, max_seats=2, max_seq_len=24, prefill_chunk=8,
              kv_dtype=kv_dtype)

    def run(num_pages):
        eng = PagedServingEngine(cfg, params, num_pages=num_pages, **kw)
        for p, g in reqs:
            eng.submit(p, max_new_tokens=g)
        return eng, {r.rid: r.generated for r in eng.run()}

    _, ref = run(32)
    tight, out = run(7)
    assert tight.metrics.preemptions >= 1
    assert out == ref


def test_full_precision_pool_unchanged_by_quantization_plumbing(setup):
    """kv_dtype=None threads through the same code paths but keeps the
    plain two-leaf cache and page-count budget arithmetic."""
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, page_size=4, num_pages=16,
                             max_seats=2, max_seq_len=20, prefill_chunk=8)
    leaves = eng.cache["pos0"]
    assert set(leaves) == {"k", "v"}
    assert leaves["k"].dtype == jnp.dtype(cfg.compute_dtype)
    assert eng.kv_dtype in ("f32", "bf16")
    assert eng.metrics.page_bytes == M.paged_page_bytes(cfg, 4)


# -- per-class precision floors -----------------------------------------------

def test_class_precision_floor_rejects_at_submit(setup):
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, page_size=4, num_pages=12,
                             max_seats=2, max_seq_len=20, prefill_chunk=8,
                             kv_dtype="fp8",
                             class_precision={"premium": "bf16"})
    with pytest.raises(ValueError, match="premium"):
        eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=2,
                   priority="premium")
    eng.submit(np.arange(4, dtype=np.int32) % cfg.vocab_size,
               max_new_tokens=2, priority="standard")
    eng.run()


def test_class_precision_validation(setup):
    cfg, params = setup
    kw = dict(page_size=4, num_pages=12, max_seats=2, max_seq_len=20,
              prefill_chunk=8)
    with pytest.raises(ValueError):
        PagedServingEngine(cfg, params,
                           class_precision={"vip": "f32"}, **kw)
    with pytest.raises(ValueError):
        PagedServingEngine(cfg, params,
                           class_precision={"premium": "fp4"}, **kw)
    with pytest.raises(ValueError):
        PagedServingEngine(cfg, params, kv_dtype="fp7", **kw)


# -- byte-denominated host budget ---------------------------------------------

def test_host_budget_weighs_engines_by_page_bytes():
    budget = HostBudget(8, page_bytes=4)        # 32 bytes total
    assert budget.total_bytes == 32
    exp = BlockManager(num_pages=9, page_size=4, page_bytes=4)
    cheap = BlockManager(num_pages=25, page_size=4, page_bytes=1)
    budget.register("exp", exp, floor=2)        # 8 bytes guaranteed
    budget.register("cheap", cheap, floor=4)    # 4 bytes guaranteed
    assert budget.surplus_bytes == 20
    assert budget.surplus == 5                  # in 4-byte reference pages
    # the cheap engine can borrow 4x as many pages from the same surplus
    got = cheap.alloc(24, rid=0)                # floor 4 + 20 borrowed
    assert got is not None
    assert budget.borrowed_bytes("cheap") == 20
    assert not budget.allows("exp", 3)          # surplus is spoken for
    assert budget.allows("exp", 2)              # floor is always grantable
    cheap.free(got[:20])
    assert budget.allows("exp", 7)              # 5 surplus pages freed up


def test_fleet_mixed_precision_routing_and_budget(setup):
    cfg, params = setup
    fleet = ModelFleet(
        [FleetModel("q", cfg, params, replicas=2, kv_dtype=[None, "fp8"])],
        total_pages=64, page_size=4, max_seats=2, max_seq_len=32,
        prefill_chunk=8, class_precision={"premium": "bf16"})
    e_full, e_q = fleet.group("q").engines
    assert (e_full.kv_dtype, e_q.kv_dtype) == ("bf16", "fp8")
    # same byte surplus buys the quantized replica more physical pages
    assert e_q.policy.bm.capacity > e_full.policy.bm.capacity
    rids = [fleet.submit(model="q", prompt=[1, 2, 3], max_new_tokens=2,
                         priority="premium") for _ in range(3)]
    assert all(fleet.route(r) == ("q", 0) for r in rids)
    rid_b = fleet.submit(model="q", prompt=[4, 5], max_new_tokens=2,
                         priority="batch")
    done = fleet.run()
    assert set(done) == set(rids) | {rid_b}
    u = fleet.budget.usage()
    assert u["total_bytes"] == 64 * M.paged_page_bytes(cfg, 4)
    assert all(e["bytes_in_use"] == 0 for e in u["engines"].values())


def test_fleet_rejects_unmeetable_class_floor(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="premium"):
        ModelFleet([FleetModel("q", cfg, params, kv_dtype="fp8")],
                   total_pages=32, page_size=4, max_seq_len=32,
                   class_precision={"premium": "bf16"})


def test_fleet_precision_floor_overrides_session_affinity(setup):
    cfg, params = setup
    fleet = ModelFleet(
        [FleetModel("q", cfg, params, replicas=2, kv_dtype=["fp8", None])],
        total_pages=64, page_size=4, max_seats=2, max_seq_len=32,
        prefill_chunk=8, class_precision={"premium": "bf16"},
        selection="round-robin")
    a = fleet.submit(model="q", prompt=[1, 2], max_new_tokens=1,
                     session_id="s1")
    assert fleet.route(a) == ("q", 0)           # pinned to the fp8 replica
    b = fleet.submit(model="q", prompt=[1, 2], max_new_tokens=1,
                     session_id="s1", priority="premium")
    assert fleet.route(b) == ("q", 1)           # floor beats the pin
    fleet.run()
