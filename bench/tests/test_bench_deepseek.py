"""DeepSeek-V2 (latent attention, an expert share, a leading dense layer,
yarn rope) against its plain reference at a tiny size, on the CPU:
chunked paged prefill and then decode through the latent pool give the
reference's full-forward logits; the fused tick emits the reference's
greedy tokens; the expert shares add up to the uncut layer; and a whole
harness run of a tiny cell is correct, its control and five planted
faults not."""
import deepseek_tiny as T  # noqa: F401  (paths)

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import catalog
import harness
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.parallel.sharding import SINGLE_DEVICE_RULES
from test_bench_faults import (_half_batch, _shift_decode_tokens,
                               _shift_prefill_logits, _stale_decode_state,
                               _stale_prefill_state)

PROMPT, DECODE, PAGE, PAGES, CHUNK = 40, 6, 16, 12, 16


def _ref_logits(params, m, seq):
    h = T.REF.hidden(params, jnp.asarray(seq)[None], m, fp8=False)[0]
    return np.asarray(jnp.einsum("td,dv->tv", h,
                                 params["lm_head"].astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST))


def _setup(impl, seed):
    m = T.model(compute_dtype="float32")
    params = T.params(m, seed, jnp.float32)
    cfg = harness.program_config(T.config(m))
    # q_chunk 8 splits each 16-token prefill chunk into two query blocks
    opts = M.RunOptions(paged_attn_impl=impl, q_chunk=8)
    n = -(-(PROMPT + DECODE) // PAGE)
    table = jnp.arange(1, 1 + n, dtype=jnp.int32)[None]
    step = jax.jit(lambda c, t, q, nv: M.paged_decode_step(
        params, cfg, c, t, q, table, nv, SINGLE_DEVICE_RULES, opts))
    return m, params, cfg, opts, table, step


def _prefill(step, cache, seq):
    out = []
    for s in range(0, PROMPT, CHUNK):
        chunk = np.zeros((1, CHUNK), np.int32)
        c = min(CHUNK, PROMPT - s)
        chunk[0, :c] = seq[s:s + c]
        lg, cache = step(cache, jnp.asarray(chunk), jnp.asarray([s]),
                         jnp.asarray([c]))
        out.append(np.asarray(lg[0, :c]))
    return out, cache


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_paged_prefill_and_decode_match_reference(impl):
    # float32 on both sides: what is compared is the maths (latent and
    # rope split, kv norm on the latent only, yarn frequencies, the
    # mscale^2 scale, the absorbed decode, the expert share, the leading
    # dense layer, the untied head), so the two orders of summation may
    # differ by rounding only
    m, params, cfg, _, _, step = _setup(impl, 3)
    seq = np.random.default_rng(0).integers(0, m["vocab_size"],
                                            PROMPT + DECODE).astype(np.int32)
    out, cache = _prefill(step, M.init_paged_cache(cfg, PAGES, PAGE), seq)
    for p in range(PROMPT, PROMPT + DECODE):
        lg, cache = step(cache, jnp.asarray([[seq[p]]], jnp.int32),
                         jnp.asarray([p]), jnp.asarray([1]))
        out.append(np.asarray(lg[0]))
    got = np.concatenate(out)
    want = _ref_logits(params, m, seq)
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err < 1e-4, err


def test_fused_tick_emits_reference_greedy_tokens():
    """Prefill, then the fused tick (model step and device sampler, jnp
    path) feeding back its own greedy tokens: each is the reference's
    best at its position, to rounding (f32 both sides)."""
    m, params, cfg, opts, table, step = _setup("jnp", 4)
    prompt = np.random.default_rng(1).integers(
        0, m["vocab_size"], PROMPT).astype(np.int32)
    out, cache = _prefill(step, M.init_paged_cache(cfg, PAGES, PAGE), prompt)
    tick = jax.jit(lambda c, last, q, st: M.fused_decode_tick(
        params, cfg, c, last, q, table, jnp.ones((1,), jnp.int32),
        jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
        jnp.ones((1,), jnp.float32), jnp.zeros((1,), jnp.uint32),
        jnp.zeros((1,), jnp.uint32), st, SINGLE_DEVICE_RULES, opts))
    last = jnp.asarray([int(out[-1][-1].argmax())], jnp.int32)
    pos, st = jnp.asarray([PROMPT], jnp.int32), jnp.zeros((1,), jnp.uint32)
    toks = [int(last[0])]
    for _ in range(DECODE - 1):
        last, cache, pos, st, _ = tick(cache, last, pos, st)
        toks.append(int(last[0]))
    seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
    ref = _ref_logits(params, m, seq)[PROMPT - 1:]
    gap = ref.max(-1) - ref[np.arange(DECODE), toks]
    assert gap.max() < 1e-4 * np.abs(ref).max(), gap


def test_expert_shares_add_up_to_the_uncut_layer():
    """The expert-share test: 16 experts over 8 shares of 2.  Each share
    routes over all 16 and computes its own two; the shares' results,
    with the shared expert (which every share computes alike) counted
    once, add up to the reference's uncut layer.  A layer holds its
    first experts, so share s sees the router's columns rolled to put
    experts 2s and 2s + 1 first (top-k routing does not depend on the
    experts' order)."""
    E, held, shares = 16, 2, 8
    m = T.model(compute_dtype="float32", moe={"experts_held": None})
    w = jax.tree.map(lambda a: a[0].astype(jnp.float32),
                     T.params(m, 7, jnp.float32)["blocks"]["pos0"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 24, m["d_model"]))
    want = np.asarray(T.REF.moe(w, h, m))
    cfg = harness.program_config(T.config(m))
    sh = w["shared"]
    shared = np.asarray(jnp.einsum(
        "btf,fd->btd", jax.nn.silu(h @ sh["wg"]) * (h @ sh["wu"]), sh["wd"],
        precision=jax.lax.Precision.HIGHEST))
    total = -(shares - 1) * shared
    for s in range(shares):
        part = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, experts_held=held))
        ws = dict(w, router=jnp.roll(w["router"], -s * held, axis=1),
                  **{k: w[k][s * held:(s + 1) * held]
                     for k in ("wg", "wu", "wd")})
        with jax.default_matmul_precision("highest"):
            got = np.asarray(moe_mod._moe_local(ws, part, h))
        if s == 0:
            # one share alone is not the layer
            assert np.abs(got - want).max() > 1e-2
        total = total + got
    assert E == held * shares
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


SECONDS = 1.5


def _run(cell, control=False):
    return harness.run(bench_tiny.ROOT, cell.name, 2 ** 31 + 19, SECONDS,
                       False, t_process=time.perf_counter(),
                       require_tpu=False, control=control, cell=cell,
                       cache=False, peaks=bench_tiny.PEAKS)


def test_tiny_cell_is_correct_and_its_control_is_not():
    """The whole harness at the long-context cell's own limit, at a size
    at which program and control read apart as on the chip: eight
    layers (one dense), width 256, 8192 tokens, 16 experts of which 8
    are held, top 6."""
    limit = catalog.Catalog(bench_tiny.ROOT).cell(
        "deepseek-v2-lite.longctx_batch").settings["check"]["max_logit_gap"]
    cell = T.batch_cell(limit, T.model(
        num_layers=8, d_model=256, num_heads=8, kv_lora_rank=128,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32, d_ff=512,
        vocab_size=8192, moe={"top_k": 6, "d_ff_expert": 128,
                              "d_ff_shared": 256}))
    res = _run(cell, control=True)
    g = res["gaps"]
    assert res["line"]["correct"], g
    assert g["tokens"] > 20 and res["line"]["failed"] == 0
    assert res["control"]["correct"] is False, res["control"]
    assert g["control_max_gap"] > limit, g


@pytest.mark.parametrize("fault", [_shift_decode_tokens,
                                   _shift_prefill_logits,
                                   _stale_decode_state,
                                   _stale_prefill_state,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    line = _run(T.batch_cell())["line"]
    assert not line["correct"], line["check"]


def test_sound_tiny_cell_is_correct():
    res = _run(T.batch_cell())
    assert res["line"]["correct"], res["line"]["check"]
    assert res["gaps"]["tokens"] > 20


def _mla_run():
    """Two decode ticks of the DeepSeek cell on a synthetic trace: the
    latent kernel inside each tick program, 1 µs and 3 µs of it."""
    import json
    import os
    model = json.load(open(os.path.join(
        bench_tiny.BENCH, "configs", "deepseek-v2-lite.json")))["model"]
    ops = [["fusion.1", 100, 50],
           ["paged_decode_attention_latent.3", 150, 1000],
           ["fusion.1", 2000, 40],
           ["paged_decode_attention_latent.3", 2050, 3000]]
    modules = [["jit__lambda", 100, 2000], ["jit__lambda", 2000, 4000]]
    trace = {"devices": [{"name": "/device:TPU:0", "ops": ops,
                          "modules": modules}],
             "host": [["bench.window", 0, 10000]]}
    steps = [harness.StepObs(0, 1, 2, 10, 100, 0, lens, len(lens), 0)
             for lens in ([1000, 2000], [1001, 2001])]
    return harness.Run("deepseek-v2-lite.longctx_batch", model, {}, {},
                       bench_tiny.PEAKS, 1.0, 0.0, 1.0, steps, [], 0, 1.0,
                       trace)


def _reader(name):
    return catalog.Catalog(bench_tiny.ROOT).reader(name)


def test_latent_kernel_roofline_counts_live_rows_once_for_all_heads():
    run = _mla_run()
    H, row, r, L = 16, 512 + 64, 512, 27
    need = 0.0
    for lens in ([1000, 2000], [1001, 2001]):
        nbytes = sum(2 * (n * row + H * row + H * r) for n in lens)
        flops = sum(2 * H * n * (row + r) for n in lens)
        need += max(nbytes / 819e9, flops / 197e12) * L
    got = _reader("latent_attn_roofline.mla")(run)
    assert got == pytest.approx(100 * need / 4e-6)
    # a trace without the latent kernel reads nothing
    run.trace["devices"][0]["ops"] = [["paged_decode_attention_pallas.3",
                                       150, 1000]]
    assert _reader("latent_attn_roofline.mla")(run) is None


def test_decode_mfu_counts_the_share_of_the_model_each_token_uses():
    run = _mla_run()
    D, H, V, L = 2048, 16, 102400, 27
    attn = D * H * 192 + D * 576 + 512 * H * 256 + H * 128 * D
    per_token = 2 * (L * attn + 3 * D * 10944
                     + 26 * (D * 64 + 3 * D * 2816 + 6 * 8 / 64 * 3 * D
                             * 1408) + D * V)
    assert per_token / 2 == pytest.approx(1270.48e6, rel=1e-5)
    flops = sum(per_token + 2 * H * (576 + 512) * n * L
                for lens in ([1000, 2000], [1001, 2001]) for n in lens)
    got = _reader("decode_mfu.mla")(run)
    assert got == pytest.approx(100 * flops / (6e-6 * 197e12))
    assert _reader("decode_tick_ms.batch")(run) == pytest.approx(3e-3)

