"""One-dispatch decode tick: the fused device-resident path must be
token-identical to the pre-fusion per-tick engine (``fused=False``), the
device sampler must match the numpy oracle draw-for-draw, and churn
(admission / finish / preemption) must never retrace the fused jit; the
tick's device work carries its named scopes, and the step's phases reach
the tick profiler."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.models import model as M
from repro.runtime import sampler as sampler_mod
from repro.runtime.sampler import Sampler, SamplingParams
from repro.runtime.serving import PagedServingEngine, ServingEngine
from repro.runtime.telemetry import Telemetry

#: the named scopes of the fused tick's device work, which a profile
#: reads from the HLO ``op_name`` metadata
TICK_SCOPES = ("kv_write", "kv_relayout", "paged_attention", "attn_proj",
               "mlp", "lm_head", "sample")
#: the step phases, each a ``serve.<phase>`` span and a profiler entry
STEP_PHASES = ("admission", "prefill", "prefill/first_token", "decode",
               "decode/grow", "decode/sync", "decode/dispatch",
               "decode/host", "decode/sample", "bookkeeping")


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config(get_config("qwen3-1.7b"))
    params = M.init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _mixed_sampling(n):
    """Alternating greedy / temperature / top-k / top-p requests."""
    variants = [SamplingParams(),
                SamplingParams(temperature=0.8, seed=11),
                SamplingParams(temperature=1.2, top_k=7, seed=22),
                SamplingParams(temperature=0.7, top_p=0.85, seed=33)]
    return [variants[i % len(variants)] for i in range(n)]


# -- device sampler vs numpy oracle ------------------------------------------

def test_device_sampler_matches_oracle():
    """sample_tokens (batched, jitted) draws the exact token the numpy
    Sampler draws for every row, across greedy/temperature/top-k/top-p
    and many (seed, rid, step) keys."""
    rng = np.random.default_rng(0)
    oracle = Sampler()
    B, V = 32, 97
    for trial in range(6):
        logits = rng.normal(scale=3.0, size=(B, V)).astype(np.float32)
        temp = rng.choice([0.0, 0.5, 0.9, 1.3], size=B).astype(np.float32)
        top_k = rng.choice([0, 1, 5, 40, V], size=B).astype(np.int32)
        top_p = rng.choice([1.0, 0.95, 0.6, 0.3], size=B).astype(np.float32)
        seed = rng.integers(0, 2**31, size=B, dtype=np.int64)
        rid = rng.integers(0, 10_000, size=B, dtype=np.int64)
        step = rng.integers(0, 4096, size=B, dtype=np.int64)
        got = np.asarray(jax.jit(sampler_mod.sample_tokens)(
            jnp.asarray(logits), jnp.asarray(temp), jnp.asarray(top_k),
            jnp.asarray(top_p), jnp.asarray(seed.astype(np.uint32)),
            jnp.asarray(rid.astype(np.uint32)),
            jnp.asarray(step.astype(np.uint32))))
        for i in range(B):
            sp = SamplingParams(temperature=float(temp[i]),
                                top_k=int(top_k[i]), top_p=float(top_p[i]),
                                seed=int(seed[i]))
            want = oracle.sample(logits[i], sp, rid=int(rid[i]),
                                 step=int(step[i]))
            assert int(got[i]) == want, (trial, i, sp)


# -- fused engine vs per-tick oracle -----------------------------------------

def _run(cfg, params, fused, prompts, gens, sps, **kw):
    eng = PagedServingEngine(cfg, params, fused=fused, **kw)
    for p, g, sp in zip(prompts, gens, sps):
        eng.submit(p, max_new_tokens=g, sampling=sp)
    done = eng.run()
    return eng, {r.rid: r.generated for r in done}


def test_fused_matches_per_tick_mixed_sampling(setup):
    """Concurrent requests with mixed greedy/stochastic sampling, seat
    contention and chunked prefill: the fused tick must reproduce the
    per-tick engine's token streams exactly."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in rng.integers(3, 20, size=6)]
    gens = [int(g) for g in rng.integers(2, 9, size=6)]
    sps = _mixed_sampling(6)
    kw = dict(page_size=8, num_pages=16, max_seats=2, max_seq_len=32,
              prefill_chunk=4)
    _, got = _run(cfg, params, True, prompts, gens, sps, **kw)
    _, want = _run(cfg, params, False, prompts, gens, sps, **kw)
    assert got == want


def test_fused_matches_per_tick_under_preemption(setup):
    """Page pressure forces preempt-and-recompute (stochastic replay
    must re-derive the same (seed, rid, step) streams); fused and
    per-tick engines must still agree token-for-token."""
    cfg, params = setup
    prompts = [(np.arange(8, dtype=np.int32) * (3 + 4 * k)) % cfg.vocab_size
               for k in range(2)]
    gens = [20, 20]
    sps = [SamplingParams(temperature=0.9, seed=5),
           SamplingParams(temperature=1.1, top_k=11, seed=6)]
    kw = dict(page_size=4, num_pages=8, max_seats=2, max_seq_len=28,
              prefill_chunk=8)
    ef, got = _run(cfg, params, True, prompts, gens, sps, **kw)
    eo, want = _run(cfg, params, False, prompts, gens, sps, **kw)
    assert eo.metrics.preemptions > 0     # scenario actually preempts
    assert ef.metrics.preemptions == eo.metrics.preemptions
    assert got == want


def test_fused_no_retrace_across_churn(setup):
    """Admission, finish and preemption churn must reuse ONE fused-tick
    trace: every argument keeps a fixed (max_seats,)-based shape, so the
    jit cache stays at a single entry for the whole run."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    eng = PagedServingEngine(cfg, params, page_size=4, num_pages=8,
                             max_seats=2, max_seq_len=28, prefill_chunk=8)
    for k in range(5):                    # staggered lengths/budgets
        eng.submit(rng.integers(0, cfg.vocab_size, 4 + 3 * k)
                   .astype(np.int32), max_new_tokens=3 + 2 * k,
                   sampling=_mixed_sampling(5)[k])
    done = eng.run()
    assert len(done) == 5
    assert eng.policy._fused_fn._cache_size() == 1


def test_fused_steady_state_single_roundtrip(setup):
    """Between churn events the fused tick must not re-upload host
    state: _sync_device runs only when the dirty flag was set by
    admit/finish/preempt/grow/prefill-completion, never per tick."""
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, page_size=8, num_pages=16,
                             max_seats=2, max_seq_len=64, prefill_chunk=8)
    calls = {"n": 0}
    orig = eng.policy._sync_device

    def counting():
        calls["n"] += 1
        orig()

    eng.policy._sync_device = counting
    eng.submit(np.arange(8, dtype=np.int32), max_new_tokens=30)
    done = eng.run()
    assert len(done) == 1 and len(done[0].generated) == 30
    # 30 decode ticks; syncs only on churn: admission/prefill completion
    # plus one per lazy page-growth boundary — far fewer than ticks
    assert calls["n"] < 10


def test_first_tokens_batched_share_timestamp(setup):
    """An admission burst samples all its first tokens in one batched
    call and timestamps after it — every request admitted in the same
    tick records the identical TTFT timestamp (no serialized
    per-request syncs inflating later requests' TTFT)."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, slots=3, max_len=32)
    for k in range(3):
        eng.submit((np.arange(5, dtype=np.int32) + k) % cfg.vocab_size,
                   max_new_tokens=2)
    done = eng.run()
    stamps = {r.t_first_token for r in done}
    assert len(stamps) == 1


# -- names a profile reads ----------------------------------------------------

@pytest.fixture(scope="module")
def tick_hlo(setup):
    """The fused tick lowered through the Pallas paged kernel (interpret
    mode off the chip), with its debug locations."""
    cfg, params = setup
    eng = PagedServingEngine(cfg, params, page_size=8, num_pages=16,
                             max_seats=2, max_seq_len=64, prefill_chunk=8,
                             opts=M.RunOptions(paged_attn_impl="pallas"))
    pol = eng.policy
    pol._sync_device()
    d = pol._dev
    return pol._fused_fn.lower(
        pol.params, pol.cache, d["last"], d["pos"], d["table"], d["nv"],
        d["temp"], d["top_k"], d["top_p"], d["seed"], d["rid"],
        d["step"]).as_text(debug_info=True)


@pytest.mark.parametrize("scope", TICK_SCOPES)
def test_fused_tick_carries_named_scope(tick_hlo, scope):
    """Each named scope reaches the op names of the lowered tick, as a
    whole component of the name stack."""
    assert re.search(rf'["/]{scope}/', tick_hlo), scope


@pytest.mark.parametrize("fused", [True, False])
def test_tick_profiler_reports_every_phase(setup, fused):
    """With a tick profiler attached the step reports each phase (the
    paged decode's sub-phases included) once a tick at most, and serves
    the tokens it serves without telemetry."""
    cfg, params = setup
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in (6, 13, 9)]
    kw = dict(page_size=4, num_pages=32, max_seats=2, max_seq_len=40,
              prefill_chunk=8)
    sps = _mixed_sampling(3)
    _, want = _run(cfg, params, fused, prompts, [7, 5, 9], sps, **kw)
    tel = Telemetry(profile=True)
    eng, got = _run(cfg, params, fused, prompts, [7, 5, 9], sps,
                    telemetry=tel, **kw)
    assert got == want
    snap = tel.profiler.snapshot()
    assert snap["ticks"] == eng._tick
    assert set(snap["phases"]) == set(STEP_PHASES)
    for name, ph in snap["phases"].items():
        assert 0 < ph["calls"] <= snap["ticks"], name
        assert ph["total_s"] >= 0
