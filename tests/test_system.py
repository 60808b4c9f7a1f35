"""End-to-end system tests: the train driver trains (loss ↓), checkpoints
restart exactly, the serve driver generates, mixed-precision training path
runs, and the paper's headline claims hold in miniature."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.train import train
from repro.launch.serve import serve


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    ck = str(tmp_path_factory.mktemp("ckpt"))
    losses = train("qwen3-1.7b", steps=30, batch=8, seq=64, reduced=True,
                   ckpt_dir=ck, ckpt_every=10, log_every=1000,
                   lr_peak=3e-3, total_steps=300)
    return ck, losses


def test_training_reduces_loss(trained):
    _, losses = trained
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first - 0.05, (first, last)


def test_restart_continues_from_checkpoint(trained):
    ck, losses = trained
    more = train("qwen3-1.7b", steps=33, batch=8, seq=64, reduced=True,
                 ckpt_dir=ck, ckpt_every=100, log_every=1000,
                 lr_peak=3e-3, total_steps=300)
    # resumed run only covers steps 30..32
    assert len(more) == 3
    assert np.isfinite(more).all()
    assert np.mean(more) < np.mean(losses[:5])


def test_injected_failure_then_recovery(tmp_path):
    """Crash mid-run, restart, and the stream replays deterministically."""
    ck = str(tmp_path / "ck")
    with pytest.raises(RuntimeError, match="injected failure"):
        train("mamba2-130m", steps=20, batch=4, seq=64, reduced=True,
              ckpt_dir=ck, ckpt_every=5, fail_at_step=12, log_every=1000)
    # recovery resumes from the last committed step (10), not zero
    losses = train("mamba2-130m", steps=14, batch=4, seq=64, reduced=True,
                   ckpt_dir=ck, ckpt_every=100, log_every=1000)
    assert len(losses) == 4                     # steps 10..13
    assert np.isfinite(losses).all()


def test_serve_generates_tokens():
    r = serve("qwen3-1.7b", batch=2, prompt_len=16, gen=8)
    gen = np.asarray(r["generated"])
    assert gen.shape == (2, 8)
    assert (gen >= 0).all()
    assert r["tokens_per_s"] > 0


def test_serve_greedy_deterministic():
    r1 = serve("llama3-8b", batch=2, prompt_len=12, gen=6, seed=3)
    r2 = serve("llama3-8b", batch=2, prompt_len=12, gen=6, seed=3)
    assert np.array_equal(np.asarray(r1["generated"]),
                          np.asarray(r2["generated"]))


def test_tuning_preset_env(tmp_path):
    """build_tuning_env is pure, idempotent, and append-only: tcmalloc
    joins (never clobbers) LD_PRELOAD, XLA flags join XLA_FLAGS, a
    missing tcmalloc library degrades to the XLA flags alone, and an
    already-tuned environment gets no additions."""
    from repro.launch.serve import build_tuning_env
    lib = tmp_path / "libtcmalloc.so.4"
    lib.write_bytes(b"")

    assert build_tuning_env("off", {}) == {}
    with pytest.raises(ValueError, match="preset"):
        build_tuning_env("warp-speed", {})

    add = build_tuning_env("alloc", {}, tcmalloc_path=str(lib))
    assert add["LD_PRELOAD"] == str(lib)
    assert "XLA_FLAGS" not in add

    add = build_tuning_env("full", {"LD_PRELOAD": "/other.so",
                                    "XLA_FLAGS": "--xla_foo=2"},
                           tcmalloc_path=str(lib))
    assert add["LD_PRELOAD"] == f"/other.so:{lib}"
    assert "--xla_foo=2" in add["XLA_FLAGS"]
    assert ("--xla_step_marker_location=STEP_MARK_AT_TOP_LEVEL_WHILE_LOOP"
            in add["XLA_FLAGS"])
    assert "--xla_force_host_platform_device_count=1" in add["XLA_FLAGS"]

    # no tcmalloc on disk: alloc adds nothing, full still tunes XLA
    assert build_tuning_env("alloc", {},
                            tcmalloc_path=str(tmp_path / "nope.so")) == {}
    add = build_tuning_env("full", {},
                           tcmalloc_path=str(tmp_path / "nope.so"))
    assert set(add) == {"XLA_FLAGS"}

    # idempotent against an environment the preset already shaped
    tuned = {"LD_PRELOAD": str(lib),
             "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
             "XLA_FLAGS": ("--xla_step_marker_location="
                           "STEP_MARK_AT_TOP_LEVEL_WHILE_LOOP "
                           "--xla_force_host_platform_device_count=1")}
    assert build_tuning_env("full", tuned, tcmalloc_path=str(lib)) == {}


@pytest.mark.parametrize("env_dir", [None, "/srv/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and no other path is set; without
    it the cache goes to the fixed .jax_cache/ at the checkout root."""
    from repro.launch import compile_cache
    set_paths = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: set_paths.append((name, value)))
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    path = compile_cache.enable_compile_cache()
    if env_dir is None:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert set_paths == [("jax_compilation_cache_dir", path)]
    else:
        assert path == env_dir
        assert set_paths == []


def test_paper_headline_lowprec_claim():
    """Table 9's structural claim in miniature: the FP8/bf16 LU does the
    same O(n³) factor work at lower precision and IR recovers an answer
    that passes the same validation gate as full-precision HPL.  (The
    paper's 10× wall-clock win needs FP8 compute units; timing is NOT
    asserted on CPU — see benchmarks/run.py table9 note.)"""
    from repro.core.hplmxp import run_hplmxp
    from repro.core.hpl import run_hpl
    hpl = run_hpl(256, 64)
    mxp = run_hplmxp(256, 64, lowprec="bf16", ir_iters=6)
    assert hpl["passed"] and mxp["passed"]
    # refinement monotone-ish: final residual <= first
    assert mxp["ir_history"][-1] <= mxp["ir_history"][0]
    # IR work is O(n²)/iter vs O(n³) factorization: at the paper's scale
    # (Table 9, N=2,989,056) refinement is noise — structural check
    n_paper = 2_989_056
    ir_flops = 6 * 3 * 2 * n_paper ** 2   # iters × (matvec + 2 tri-solves)
    assert ir_flops < (2 / 3) * n_paper ** 3 * 1e-3
