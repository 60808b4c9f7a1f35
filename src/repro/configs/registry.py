"""Architecture registry + per-cell input specs.

``get_config(arch_id)`` returns the exact assigned ModelConfig;
``input_specs(cfg, shape)`` returns allocation-free ShapeDtypeStruct
stand-ins for every model input of that (arch × shape) cell — the dry-run
feeds these straight into ``jax.jit(...).lower()``.
"""
from __future__ import annotations

import importlib
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig, SHAPES, reduced_config

_MODULES = {
    "minicpm-2b": "minicpm_2b",
    "llama3-8b": "llama3_8b",
    "qwen3-1.7b": "qwen3_1_7b",
    "gemma3-12b": "gemma3_12b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "grok-1-314b": "grok_1_314b",
    "mamba2-130m": "mamba2_130m",
    "whisper-base": "whisper_base",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "phi-3-vision-4.2b": "phi_3_vision_4_2b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}

ARCH_IDS = tuple(_MODULES)

# module-style aliases: "llama3_8b" -> "llama3-8b" etc., so CLI specs can
# use either the registry id or the config module's name
_ALIASES = {mod: arch for arch, mod in _MODULES.items()}


def resolve_arch(name: str) -> str:
    """Canonical registry id for ``name`` — the id itself or a
    module-style alias (``llama3_8b`` for ``llama3-8b``).

    Raises:
      KeyError: unknown name; the message lists every known id and
          alias so CLI flag errors are self-explanatory."""
    if name in _MODULES:
        return name
    if name in _ALIASES:
        return _ALIASES[name]
    raise KeyError(f"unknown model {name!r}; known: {sorted(_MODULES)} "
                   f"(aliases: {sorted(_ALIASES)})")


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.CONFIG


def get_shape(shape_id: str) -> ShapeConfig:
    return SHAPES[shape_id]


def cell_supported(cfg: ModelConfig, shape: ShapeConfig) -> Tuple[bool, str]:
    """Whether this (arch × shape) cell runs, per the assignment rules."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, ("skip: long_500k needs sub-quadratic attention; "
                       f"{cfg.name} is pure full-attention")
    return True, ""


def input_specs(cfg: ModelConfig, shape: ShapeConfig,
                reduced: Optional[ModelConfig] = None) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for the batch of one cell.

    For decode cells this is the *per-step* input (tokens + positions); the
    KV cache is produced separately by ``repro.models.cache_specs``.
    """
    c = reduced or cfg
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = jnp.float32
    if shape.kind == "decode":
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, 1), i32),
            "pos": jax.ShapeDtypeStruct((B,), i32),
        }
        return specs
    specs = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    if shape.kind == "train":
        specs["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    if c.frontend == "vision":
        specs["patches"] = jax.ShapeDtypeStruct((B, c.frontend_len, c.frontend_dim), f32)
    if c.frontend == "audio":
        specs["audio"] = jax.ShapeDtypeStruct((B, c.encoder_len, c.frontend_dim), f32)
    return specs


def input_axes(cfg: ModelConfig, shape: ShapeConfig,
               seq_sharded: bool = False) -> Dict[str, tuple]:
    """Logical axes per input array (feeds parallel.sharding.spec_for)."""
    c = cfg
    seq = "seq_shard" if seq_sharded else None
    if shape.kind == "decode":
        return {"tokens": ("batch", None), "pos": ("batch",)}
    axes = {"tokens": ("batch", seq)}
    if shape.kind == "train":
        axes["labels"] = ("batch", seq)
    if c.frontend == "vision":
        axes["patches"] = ("batch", None, None)
    if c.frontend == "audio":
        axes["audio"] = ("batch", None, None)
    return axes


def make_example_batch(cfg: ModelConfig, shape_kind: str, batch: int, seq: int,
                       key=None) -> Dict[str, jnp.ndarray]:
    """Small concrete batch for smoke tests / examples."""
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    out = {"tokens": jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size, jnp.int32)}
    if shape_kind == "train":
        out["labels"] = jax.random.randint(k2, (batch, seq), 0, cfg.vocab_size, jnp.int32)
    if cfg.frontend == "vision":
        out["patches"] = jax.random.normal(k3, (batch, cfg.frontend_len, cfg.frontend_dim))
    if cfg.frontend == "audio":
        # distinct stream from the vision patches (k3 must not be
        # consumed twice; fold_in keeps k1-k3 streams unchanged)
        k4 = jax.random.fold_in(k3, 1)
        out["audio"] = jax.random.normal(k4, (batch, cfg.encoder_len, cfg.frontend_dim))
    return out
