"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step on CPU, asserting output shapes and finiteness (the
assignment's required smoke coverage for all 10 archs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ARCH_IDS, get_config, reduced_config,
                           make_example_batch, SHAPES, cell_supported)
from repro.models import model as M
from repro.optim.adamw import init_opt_state, adamw_update
from repro.parallel.sharding import SINGLE_DEVICE_RULES

OPTS = M.RunOptions(q_chunk=32, xent_chunk=32)


@pytest.fixture(scope="module")
def arch_setup():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = reduced_config(get_config(arch))
            params = M.init_params(M.param_specs(cfg), jax.random.PRNGKey(0))
            cache[arch] = (cfg, params)
        return cache[arch]

    return get


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_loss_finite(arch, arch_setup):
    cfg, params = arch_setup(arch)
    batch = make_example_batch(cfg, "train", 2, 64)
    loss, metrics = jax.jit(
        lambda p, b: M.lm_loss(p, cfg, b, SINGLE_DEVICE_RULES, OPTS))(params, batch)
    assert np.isfinite(float(loss))
    # next-token xent at init should be near ln(vocab)
    assert abs(float(metrics["xent"]) - np.log(cfg.vocab_size)) < 1.5


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step_improves_or_finite(arch, arch_setup):
    cfg, params = arch_setup(arch)
    batch = make_example_batch(cfg, "train", 2, 32)
    opt = init_opt_state(params)

    @jax.jit
    def step(p, o, b):
        (loss, _), g = jax.value_and_grad(M.lm_loss, has_aux=True)(
            p, cfg, b, SINGLE_DEVICE_RULES, OPTS)
        p2, o2, m = adamw_update(g, o, p, 1e-3)
        return p2, o2, loss, m["grad_norm"]

    p2, o2, loss, gnorm = step(params, opt, batch)
    assert np.isfinite(float(loss)) and np.isfinite(float(gnorm))
    assert float(gnorm) > 0
    # params actually moved
    delta = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(params)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_consistency(arch, arch_setup):
    """Decode with a prefilled cache must reproduce prefill logits."""
    cfg, params = arch_setup(arch)
    B, S = 2, 32
    batch = make_example_batch(cfg, "prefill", B, S)
    logits_p, _ = jax.jit(
        lambda p, b: M.prefill(p, cfg, b, SINGLE_DEVICE_RULES, OPTS))(params, batch)
    batch1 = {k: (v[:, :S - 1] if k == "tokens" else v) for k, v in batch.items()}
    _, cache1 = jax.jit(
        lambda p, b: M.prefill(p, cfg, b, SINGLE_DEVICE_RULES, OPTS))(params, batch1)

    def pad(path, v):
        # one more position on the sequence axis of every K/V (or latent)
        # cache; the leading dense layers' caches sit under "lead"
        if path[-1].key not in ("k", "v", "latent"):
            return v
        return jnp.concatenate(
            [v, jnp.zeros(v.shape[:2] + (1,) + v.shape[3:], v.dtype)], axis=2)

    cache1 = jax.tree_util.tree_map_with_path(pad, cache1)
    tok = batch["tokens"][:, S - 1:S]
    pos = jnp.full((B,), S - 1, jnp.int32)
    logits_d, _ = jax.jit(
        lambda p, c, t, q: M.decode_step(p, cfg, c, t, q,
                                         SINGLE_DEVICE_RULES, OPTS))(
        params, cache1, tok, pos)
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(logits_p),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_exact_assigned_config(arch):
    """The full (non-reduced) config must match the assignment table."""
    cfg = get_config(arch)
    expected = {
        "minicpm-2b": (40, 2304, 36, 36, 5760, 122753),
        "llama3-8b": (32, 4096, 32, 8, 14336, 128256),
        "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151936),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "mamba2-130m": (24, 768, 0, 0, 0, 50280),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "phi-3-vision-4.2b": (32, 3072, 32, 32, 8192, 32064),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
    }[arch]
    got = (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
           cfg.d_ff if cfg.moe is None or arch != "qwen2-moe-a2.7b"
           else cfg.moe.d_ff_expert, cfg.vocab_size)
    assert got == expected


def test_moe_expert_counts():
    q = get_config("qwen2-moe-a2.7b").moe
    assert (q.num_experts, q.top_k, q.num_shared_experts) == (60, 4, 4)
    g = get_config("grok-1-314b").moe
    assert (g.num_experts, g.top_k) == (8, 2)
    j = get_config("jamba-v0.1-52b").moe
    assert (j.num_experts, j.top_k, j.moe_every) == (16, 2, 2)


def test_jamba_layer_pattern():
    cfg = get_config("jamba-v0.1-52b")
    kinds = cfg.layer_kinds()
    assert len(kinds) == 8 and kinds.count("attn") == 1
    assert kinds[4] == "attn"                   # 1:7 attn:mamba at offset 4
    mlps = cfg.mlp_kinds()
    assert mlps.count("moe") == 4 and mlps.count("dense") == 4


def test_gemma_local_global_pattern():
    cfg = get_config("gemma3-12b")
    kinds = cfg.layer_kinds()
    assert len(kinds) == 6
    assert kinds.count("attn_local") == 5 and kinds[5] == "attn"


def test_long500k_applicability():
    long = SHAPES["long_500k"]
    runs = {a: cell_supported(get_config(a), long)[0] for a in ARCH_IDS}
    assert runs["mamba2-130m"] and runs["jamba-v0.1-52b"] and runs["gemma3-12b"]
    assert not runs["llama3-8b"] and not runs["whisper-base"]
    assert sum(runs.values()) == 3
