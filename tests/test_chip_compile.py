"""Ahead-of-time compiles of the serving path for one TPU v5e chip.

The TPU compiler is installed even where no chip is attached, so these
tests lower with ``interpret=False`` against a described ``v5e:2x2``
topology: Mosaic's block-tiling rules and the chip's HBM limit are
checked on every run without a chip.  Nothing runs and nothing is
timed.  Shapes are ``chip_smoke.py``'s engine: qwen3-1.7b at published
widths (28 layers, 16 query / 8 KV heads, head_dim 128, vocab 151,936),
f32 weights, 8 seats, 16-token pages and a 1024-page bf16 pool; the paged
decode kernel also at the two benchmark cells' shapes.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import (
    paged_decode_attention_pallas, quantized_paged_decode_attention_pallas)
from repro.models import model as M
from repro.parallel.sharding import SINGLE_DEVICE_RULES

SEATS, PAGE, PAGES, MAX_SEQ_LEN = 8, 16, 1024, 1032


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip.  The persistent compilation cache is off
    while these compile: an entry for a chip that is not attached could
    not be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(one_chip, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)


# (seats, pages, table width, query heads, KV heads, head_dim): the smoke
# engine's shapes (a 65-page table, padded to whole blocks), then the two
# benchmark cells' (bench/cells, bench/configs)
_SMOKE = (SEATS, PAGES, -(-MAX_SEQ_LEN // PAGE), 16, 8, 128)
_CELLS = {"qwen3-1.7b.chat": (32, 3335, 64, 16, 8, 128),
          "minicpm-2b.longctx_batch": (2, 710, 256, 36, 36, 64)}
_KERNEL_CASES = [pytest.param(_SMOKE, dt, id=dt)
                 for dt in ("bf16", "int8", "fp8")]
_KERNEL_CASES += [pytest.param(shape, dt, id=f"{cell}-{dt}")
                  for cell, shape in _CELLS.items()
                  for dt in ("bf16", "int8", "fp8")]


@pytest.mark.parametrize("shape,kv_dtype", _KERNEL_CASES)
def test_paged_decode_kernel_compiles_for_v5e(one_chip, shape, kv_dtype):
    B, P, n, H, KVH, d = shape
    storage = {"bf16": jnp.bfloat16, "int8": jnp.int8, "fp8": jnp.uint8}
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = S((B, KVH, H // KVH, d), jnp.bfloat16)
    pool = S((KVH, P, PAGE, d), storage[kv_dtype])
    pt, lens = S((B, n), jnp.int32), S((B,), jnp.int32)
    if kv_dtype == "bf16":
        lowered = paged_decode_attention_pallas.lower(
            q, pool, pool, pt, lens, interpret=False)
    else:
        scale = S((KVH, P, PAGE), jnp.float32)
        lowered = quantized_paged_decode_attention_pallas.lower(
            q, pool, pool, scale, scale, pt, lens, interpret=False)
    assert lowered.compile().as_text().count("tpu_custom_call") == 1


def test_fused_decode_tick_compiles_and_fits_v5e(one_chip, monkeypatch):
    """The fused tick at full width, jitted as ``PagedPolicy`` jits it
    off the CPU (cache / last / pos / table / step donated).
    ``_on_tpu`` is steered here because the process's backend is the
    CPU: without it the kernel would lower in interpret mode and no
    ``tpu_custom_call`` would reach the program.  The compiler refuses a
    program that does not fit the chip's HBM."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_config("qwen3-1.7b")
    params = _shapes(one_chip, M.abstract_params(M.param_specs(cfg)))
    cache = _shapes(one_chip, jax.eval_shape(
        lambda: M.init_paged_cache(cfg, PAGES, PAGE)))
    vec = lambda dt: jax.ShapeDtypeStruct((SEATS,), dt, sharding=one_chip)
    table = jax.ShapeDtypeStruct((SEATS, -(-MAX_SEQ_LEN // PAGE)),
                                 jnp.int32, sharding=one_chip)
    i32, f32, u32 = jnp.int32, jnp.float32, jnp.uint32
    opts = M.RunOptions(q_chunk=min(MAX_SEQ_LEN, 512))
    fn = jax.jit(
        lambda p, c, last, q, pt, nv, t, tk, tp, sd, rd, st:
            M.fused_decode_tick(p, cfg, c, last, q, pt, nv, t, tk, tp, sd,
                                rd, st, SINGLE_DEVICE_RULES, opts),
        donate_argnums=(1, 2, 3, 4, 11))
    compiled = fn.lower(params, cache, vec(i32), vec(i32), table, vec(i32),
                        vec(f32), vec(i32), vec(f32), vec(u32), vec(u32),
                        vec(u32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
