"""Whole runs of tiny cells on the CPU, the chip check left out: a
sound run comes out correct, and a run whose timed path alters a token
where it is produced comes out not correct, with a tied output head and
with an untied one (``lm_head``, laid out by the reference module)."""
import bench_tiny  # noqa: F401  (paths)

import functools
import time

import jax.numpy as jnp
import pytest

import catalog
import harness
from repro.models import model as M

SECONDS = 1.5


def _run(cell, control=False):
    return harness.run(bench_tiny.ROOT, cell.name, 2 ** 31 + 17, SECONDS,
                       False, t_process=time.perf_counter(),
                       require_tpu=False, control=control, cell=cell,
                       cache=False, peaks=bench_tiny.PEAKS)


def _shift_decode_tokens(monkeypatch):
    """The fused tick hands back each decoded token plus one."""
    real = M.fused_decode_tick

    def faulty(params, cfg, *a, **k):
        toks, *rest = real(params, cfg, *a, **k)
        return ((toks + 1) % cfg.vocab_size, *rest)

    monkeypatch.setattr(M, "fused_decode_tick", faulty)


def _shift_prefill_logits(monkeypatch):
    """The prefill step's logits rolled by one token, so the first
    token comes out wrong."""
    real = M.paged_decode_step

    def faulty(params, cfg, cache, tokens, *a, **k):
        logits, new = real(params, cfg, cache, tokens, *a, **k)
        if tokens.shape[1] > 1:
            logits = jnp.roll(logits, 1, axis=-1)
        return logits, new

    monkeypatch.setattr(M, "paged_decode_step", faulty)


def _stale_decode_state(monkeypatch):
    """The fused tick returns the KV pool it was given: the step's
    state is left unchanged."""
    real = M.fused_decode_tick

    def faulty(params, cfg, cache, *a, **k):
        toks, _, *rest = real(params, cfg, cache, *a, **k)
        return (toks, cache, *rest)

    monkeypatch.setattr(M, "fused_decode_tick", faulty)


def _stale_prefill_state(monkeypatch):
    """The prefill step returns the KV pool it was given."""
    real = M.paged_decode_step

    def faulty(params, cfg, cache, tokens, *a, **k):
        logits, new = real(params, cfg, cache, tokens, *a, **k)
        return logits, (cache if tokens.shape[1] > 1 else new)

    monkeypatch.setattr(M, "paged_decode_step", faulty)


def _half_batch(monkeypatch):
    """The fused tick leaves the even seats out (seat 0 among them, the
    one a light load fills first); they keep their last token."""
    real = M.fused_decode_tick

    def faulty(params, cfg, cache, last, pos, table, n_valid, *a, **k):
        keep = (jnp.arange(n_valid.shape[0]) % 2 == 1).astype(n_valid.dtype)
        return real(params, cfg, cache, last, pos, table, n_valid * keep,
                    *a, **k)

    monkeypatch.setattr(M, "fused_decode_tick", faulty)


CELLS = {"chat": bench_tiny.chat_cell, "batch": bench_tiny.batch_cell,
         "chat.untied": functools.partial(bench_tiny.chat_cell, tie=False),
         "batch.untied": functools.partial(bench_tiny.batch_cell,
                                           tie=False)}


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_run_is_correct(kind):
    res = _run(CELLS[kind]())
    line = res["line"]
    assert line["correct"], line["check"]
    assert res["gaps"]["tokens"] > 20
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "check"
    for m in CELLS[kind]().end_to_end:
        assert line["metrics"][m.name]["value"] > 0


@pytest.mark.parametrize("fault", [_shift_decode_tokens,
                                   _shift_prefill_logits,
                                   _stale_decode_state,
                                   _stale_prefill_state,
                                   _half_batch])
@pytest.mark.parametrize("kind", sorted(CELLS))
def test_broken_timed_path_is_not_correct(kind, fault, monkeypatch):
    fault(monkeypatch)
    line = _run(CELLS[kind]())["line"]
    assert not line["correct"], line["check"]


def _control_cell(tie):
    """The tiny chat cell at the chat cell's own limit, at a small size
    at which program and control read like the chip's full-size runs
    (about 0.04 and 0.5): eight layers, width 256, 8192 tokens."""
    limit = catalog.Catalog(bench_tiny.ROOT).cell("qwen3-1.7b.chat") \
        .settings["check"]["max_logit_gap"]
    cell = bench_tiny.chat_cell(limit, tie=tie)
    cell.config = bench_tiny.config(bench_tiny.model(
        num_layers=8, d_model=256, vocab_size=8192, num_heads=8,
        kv_heads=4, head_dim=32, d_ff=512, tie_embeddings=tie))
    return cell, limit


def test_control_fails_the_chat_limit():
    """The control (the reference in float8) against the chat cell's
    own limit."""
    cell, limit = _control_cell(tie=True)
    res = _run(cell, control=True)
    g = res["gaps"]
    assert res["line"]["correct"], g
    assert g["control_max_gap"] > limit, g
    # the control goes through the harness's own comparison
    assert res["control"]["correct"] is False, res["control"]
    assert res["control"]["check"]["max_logit_gap"] == {
        "value": g["control_max_gap"], "limit": limit}


def test_untied_cell_is_correct_and_its_control_is_not():
    """A configuration with an untied head runs through the whole
    harness with nothing but its model dict changed: the reference
    module lays out ``lm_head`` and scores against it."""
    cell, limit = _control_cell(tie=False)
    res = _run(cell, control=True)
    g = res["gaps"]
    assert res["line"]["correct"], g
    assert g["tokens"] > 20
    assert res["control"]["correct"] is False, res["control"]
    assert g["control_max_gap"] > limit, g
