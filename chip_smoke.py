"""Chip smoke test: the paged serving path of qwen3-1.7b, at its published
widths, on one TPU chip.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chips  # sharded training on a four-chip host

One chip: random f32 weights (seeded, built as ``launch.serve`` builds
them) serve 8 seeded requests, prompts of 100-1000 tokens and 32 greedy
new tokens each, through ``PagedServingEngine``: chunked prefill, then
the fused decode tick with the Pallas paged-attention kernel compiled by
Mosaic.  Checks, on the chip:

  * the paged decode kernels (bf16, int8, fp8 pools) against their jnp
    oracles in ``kernels/ref.py`` at the engine's shapes;
  * every request finishes with its 32 tokens;
  * the fused tick's compiled program holds the kernel
    (``tpu_custom_call``), so it was compiled and not interpreted;
  * one paged decode step's logits with ``paged_attn_impl="pallas"``
    against ``"jnp"`` on the engine's KV pool.

``--four-chips`` runs only the sharded training path: a few
``launch.train.train`` steps at full width on the host's data x model =
4 x 1 mesh (FSDP), a check that parameters and optimizer state are
spread over all four devices, and the step-0 loss against an unsharded
forward pass on one device.

Every phase runs in this one process, which holds the chip; it starts no
child.  Any failed check raises.  The last line of standard output is
``{"ok": true, "device": {...}}``, printed only when every phase passed;
without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ShapeConfig  # noqa: E402
from repro.core import mixed_precision as mp  # noqa: E402
from repro.data.pipeline import TokenPipeline  # noqa: E402
from repro.kernels.decode_attention import (  # noqa: E402
    paged_decode_attention_pallas, quantized_paged_decode_attention_pallas)
from repro.kernels.ref import (  # noqa: E402
    paged_decode_attention_ref, quantized_paged_decode_attention_ref)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.steps import build_cell  # noqa: E402
from repro.launch.train import make_train_state, train  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.parallel.sharding import SINGLE_DEVICE_RULES  # noqa: E402
from repro.runtime.serving import PagedServingEngine  # noqa: E402

ARCH = "qwen3-1.7b"
SEED = 0

# serving: 8 seats over 16-token pages; 1024 pages (1.75 MiB each across
# the 28 layers) is what fits beside f32 weights on a 16 GB chip
SEATS, PAGE, PAGES = 8, 16, 1024
REQUESTS, PROMPT_MIN, PROMPT_MAX, NEW_TOKENS = 8, 100, 1000, 32
PREFILL_CHUNK = 256
MAX_SEQ_LEN = PROMPT_MAX + NEW_TOKENS

# bf16 tolerances.  Kernel outputs are bf16 (8 mantissa bits): a
# rounding step is 2^-8 of the value, so allow 2e-2 absolute plus 2e-2
# relative.  The two attention paths round differently (the jnp path
# casts softmax probabilities to bf16, the kernel keeps them f32) and the
# bf16 residual stream carries that through every layer: at these widths
# the relative RMS difference of the logits was 0.012 at 2 layers, 0.023
# at 8 and 0.033 at 16 (CPU, interpret mode), growing as sqrt(layers)
# towards ~0.045 at 28.  A kernel that reads the wrong pages or scales
# differs at order 1.
KERNEL_ATOL = KERNEL_RTOL = 2e-2
LOGITS_REL_TOL = 0.1

# training: 4 x 1 host mesh, f32 weights + AdamW state (~27.6 GB) that no
# single 16 GB chip holds.  Sharded and unsharded bf16 forwards reduce in
# different orders; their losses must agree to 1e-2 relative.
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 512
LOSS_REL_TOL = 1e-2


class CompileClock:
    """Sums the backend compile time JAX reports through
    ``jax.monitoring`` (persistent-cache hits compile nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **_):
        if event == self.EVENT:
            self.seconds += secs
            self.count += 1


def require_tpu() -> dict:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _close(name, got, want, atol, rtol):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want)))
    ok = bool(np.all(np.abs(got - want) <= atol + rtol * np.abs(want)))
    print(f"[kernels] {name}: max |kernel - ref| = {err!r} "
          f"(atol {atol}, rtol {rtol}) {'ok' if ok else 'FAILED'}")
    if not ok:
        raise AssertionError(f"{name} kernel disagrees with its reference")


def check_kernels(cfg) -> None:
    """The paged decode kernels against ``kernels/ref.py`` on the chip,
    at the engine's shapes: per seat, the ``rep`` query heads of each KV
    head over a (KVH, P, page, d) pool, as ``ops.paged_decode_attention``
    lays them out."""
    H, KVH, d = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    n = math.ceil(MAX_SEQ_LEN / PAGE)
    kq, kk, kv, kp, kl = jax.random.split(jax.random.PRNGKey(SEED + 1), 5)
    q = jax.random.normal(kq, (SEATS, KVH, H // KVH, d), jnp.bfloat16)
    k = jax.random.normal(kk, (KVH, PAGES, PAGE, d), jnp.float32)
    v = jax.random.normal(kv, (KVH, PAGES, PAGE, d), jnp.float32)
    pt = jax.random.randint(kp, (SEATS, n), 0, PAGES, jnp.int32)
    lens = jax.random.randint(kl, (SEATS,), 1, n * PAGE + 1, jnp.int32)
    kb, vb = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    _close("bf16", paged_decode_attention_pallas(q, kb, vb, pt, lens),
           paged_decode_attention_ref(q, kb, vb, pt, lens),
           KERNEL_ATOL, KERNEL_RTOL)
    for kv_dtype in mp.KV_QUANTIZED:
        kq8, ks = mp.quantize_kv_page(k, kv_dtype)
        vq8, vs = mp.quantize_kv_page(v, kv_dtype)
        _close(kv_dtype,
               quantized_paged_decode_attention_pallas(q, kq8, vq8, ks, vs,
                                                       pt, lens),
               quantized_paged_decode_attention_ref(q, kq8, vq8, ks, vs,
                                                    pt, lens),
               KERNEL_ATOL, KERNEL_RTOL)


def serve(cfg, device: dict, clock: CompileClock) -> PagedServingEngine:
    """Serve the seeded requests and check every one completes."""
    t0 = time.perf_counter()
    params = M.init_params(M.param_specs(cfg), jax.random.PRNGKey(SEED),
                           dtype=jnp.float32)
    eng = PagedServingEngine(cfg, params, page_size=PAGE, num_pages=PAGES,
                             max_seats=SEATS, max_seq_len=MAX_SEQ_LEN,
                             prefill_chunk=PREFILL_CHUNK)
    rng = np.random.default_rng(SEED)
    lengths = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, REQUESTS)
    for n in lengths:
        eng.submit(rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32),
                   max_new_tokens=NEW_TOKENS)
    jax.block_until_ready(eng.cache)
    print(f"[serve] set-up {time.perf_counter() - t0!r} s: {ARCH} at "
          f"published widths, f32 weights, {PAGES} pages of {PAGE} tokens, "
          f"prompts {sorted(int(n) for n in lengths)}")

    # the decode window opens once every request has been prefilled
    t0 = time.perf_counter()
    window = None
    for _ in range(eng.default_max_ticks):
        if not (eng.queue or eng.seats):
            break
        if window is None and not eng.queue and all(
                r.prefill_pos >= len(r.prefill_src)
                for r in eng.seats.values()):
            jax.block_until_ready(eng.cache)
            window = (time.perf_counter(), eng.metrics.ticks,
                      eng.metrics.decode_tokens, clock.count)
        eng.step()
    jax.block_until_ready(eng.cache)
    t1 = time.perf_counter()
    if eng.queue or eng.seats:
        raise AssertionError("engine stalled with work pending")
    print(f"[serve] {len(eng.finished)} requests in {t1 - t0!r} s on "
          f"{device['kind']} ({clock.count} compiles, "
          f"{clock.seconds!r} s compiling)")
    if window is None:
        raise AssertionError("no decode window: a request never finished "
                             "its prefill")
    print(f"[serve] decode window on {device['kind']}: {t1 - window[0]!r} s "
          f"for {eng.metrics.ticks - window[1]} ticks, "
          f"{eng.metrics.decode_tokens - window[2]} tokens, "
          f"{clock.count - window[3]} compiles")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[serve] peak_bytes_in_use on {device['kind']}: "
          f"{stats.get('peak_bytes_in_use')!r}")

    if len(eng.finished) != REQUESTS:
        raise AssertionError(f"{len(eng.finished)} of {REQUESTS} finished")
    for r in eng.finished:
        toks = np.asarray(r.generated)
        if len(toks) != NEW_TOKENS or toks.min() < 0 \
                or toks.max() >= cfg.vocab_size:
            raise AssertionError(f"request {r.rid}: {len(toks)} tokens, "
                                 f"want {NEW_TOKENS} in [0, vocab)")
    print(f"[serve] every request returned {NEW_TOKENS} tokens")
    return eng


def check_fused_tick_compiled(eng: PagedServingEngine) -> None:
    """The fused tick the engine ran, lowered with its live arguments:
    its compiled program must hold the Mosaic kernel."""
    pol = eng.policy
    d = pol._dev
    t0 = time.perf_counter()
    hlo = pol._fused_fn.lower(
        pol.params, pol.cache, d["last"], d["pos"], d["table"], d["nv"],
        d["temp"], d["top_k"], d["top_p"], d["seed"], d["rid"],
        d["step"]).compile().as_text()
    n = hlo.count("tpu_custom_call")
    print(f"[serve] fused tick program: {n} tpu_custom_call "
          f"(compile or cache read {time.perf_counter() - t0!r} s)")
    if n == 0:
        raise AssertionError("the fused tick holds no Mosaic kernel")


def check_pallas_matches_jnp(eng: PagedServingEngine) -> None:
    """One paged decode step's logits, Pallas kernel against the jnp
    gather path, on the pool the engine left behind.  Each seat reads
    pages of its own (no two seats share a page, so the step's K/V
    writes cannot collide) at a length one of the requests reached."""
    cfg = eng.cfg
    n = eng.n_tables
    table = np.arange(1, 1 + SEATS * n, dtype=np.int32).reshape(SEATS, n)
    reqs = sorted(eng.finished, key=lambda r: r.rid)
    pos = np.asarray([len(r.prompt) + len(r.generated) - 1 for r in reqs],
                     np.int32)
    tok = np.asarray([[r.generated[-1]] for r in reqs], np.int32)
    nv = np.ones((SEATS,), np.int32)
    logits = {}
    for impl in ("pallas", "jnp"):
        opts = dataclasses.replace(eng.opts, paged_attn_impl=impl)
        step = jax.jit(lambda p, c, t, q, pt, v, o=opts: M.paged_decode_step(
            p, cfg, c, t, q, pt, v, SINGLE_DEVICE_RULES, o)[0])
        logits[impl] = np.asarray(step(eng.params, eng.cache, tok, pos,
                                       table, nv))
    a, b = logits["pallas"], logits["jnp"]
    if not (np.all(np.isfinite(a)) and np.all(np.isfinite(b))):
        raise AssertionError("non-finite logits")
    rel = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    agree = int(np.sum(a.argmax(-1) == b.argmax(-1)))
    print(f"[logits] pallas vs jnp: |diff| / |jnp| (RMS) = {rel!r} "
          f"(tol {LOGITS_REL_TOL}); argmax agrees on {agree}/{SEATS} seats")
    if rel > LOGITS_REL_TOL:
        raise AssertionError("pallas and jnp decode logits disagree")


def one_chip(device: dict, clock: CompileClock) -> None:
    cfg = get_config(ARCH)
    check_kernels(cfg)
    eng = serve(cfg, device, clock)
    check_fused_tick_compiled(eng)
    check_pallas_matches_jnp(eng)


def _bytes_per_device(tree) -> dict:
    out = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            out[shard.device] = out.get(shard.device, 0) + shard.data.nbytes
    return out


def four_chips(device: dict) -> None:
    """FSDP training on the host mesh, checked against one device."""
    if device["count"] != 4:
        raise AssertionError(f"--four-chips needs 4 devices, JAX found "
                             f"{device['count']}")
    t0 = time.perf_counter()
    losses = train(ARCH, steps=TRAIN_STEPS, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                   reduced=False)
    print(f"[train] {TRAIN_STEPS} steps in {time.perf_counter() - t0!r} s "
          f"on {device['count']} x {device['kind']}, losses {losses!r}")
    if not np.all(np.isfinite(losses)):
        raise AssertionError("non-finite training loss")

    # the placement train() used: its cell's shardings and its init key
    # (train()'s default options: the shardings do not depend on the
    # learning-rate schedule)
    cfg = get_config(ARCH)
    opts = M.RunOptions(q_chunk=min(TRAIN_SEQ, 512),
                        xent_chunk=min(TRAIN_SEQ, 512))
    mesh = make_host_mesh()
    cell = build_cell(cfg, ShapeConfig("custom", TRAIN_SEQ, TRAIN_BATCH,
                                       "train"), mesh, opts=opts)
    with mesh:
        state = make_train_state(cell, jax.random.PRNGKey(0))
    per_dev = _bytes_per_device(state)
    total = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    print(f"[train] parameters + optimizer state: {total!r} bytes; per "
          f"device {sorted((d.id, b) for d, b in per_dev.items())!r}")
    if len(per_dev) != 4 or max(per_dev.values()) > 0.3 * total:
        raise AssertionError("training state is not spread over 4 devices")
    del state

    params = M.init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
    batch = TokenPipeline(cfg.vocab_size, TRAIN_SEQ,
                          TRAIN_BATCH).get_batch(0)
    ref = float(jax.jit(lambda p, b: M.lm_loss(
        p, cfg, b, SINGLE_DEVICE_RULES, opts)[0])(params, batch))
    rel = abs(losses[0] - ref) / abs(ref)
    print(f"[train] step-0 loss {losses[0]!r} vs unsharded forward {ref!r}: "
          f"relative difference {rel!r} (tol {LOSS_REL_TOL})")
    if rel > LOSS_REL_TOL:
        raise AssertionError("sharded step-0 loss disagrees with the "
                             "unsharded forward")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training phase on 4 chips")
    args = ap.parse_args()
    cache_dir = enable_compile_cache()
    device = require_tpu()
    clock = CompileClock()
    print(f"[chip_smoke] {device['count']} x {device['kind']}, "
          f"compile cache {cache_dir}")
    if args.four_chips:
        four_chips(device)
    else:
        one_chip(device, clock)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
