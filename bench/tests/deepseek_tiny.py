"""Tiny DeepSeek-V2 configurations for the benchmark's CPU tests: latent
attention, a leading dense layer, an expert share and yarn rope at a few
thousand parameters, through the program, the harness and the
``deepseek_v2`` reference."""
import copy
import os

import bench_tiny

import catalog
import weights

REF = catalog.load_module(os.path.join(bench_tiny.BENCH, "reference",
                                       "deepseek_v2.py"),
                          "bench_reference_deepseek_v2")

YARN = {"type": "yarn", "factor": 40.0,
        "original_max_position_embeddings": 4096, "beta_fast": 32.0,
        "beta_slow": 1.0, "mscale": 0.707, "mscale_all_dim": 0.707}


def model(**over):
    """Published proportions at toy widths: 16 experts of which the
    first 8 are held, top 4, one leading dense layer."""
    moe = {"num_experts": 16, "top_k": 4, "d_ff_expert": 32,
           "num_shared_experts": 2, "d_ff_shared": 64,
           "norm_topk_prob": False, "shared_gate": False,
           "router_f32": True, "experts_held": 8}
    moe.update(over.pop("moe", {}))
    m = {"num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "d_ff": 128, "vocab_size": 256, "first_k_dense": 1,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "tie_embeddings": False, "rope_theta": 10000.0,
         "rope_scaling": dict(YARN), "norm_eps": 1e-6, "moe": moe,
         "compute_dtype": "bfloat16"}
    m.update(over)
    return m


def params(m, seed, dtype):
    return weights.make(REF.shapes(m), seed, dtype)


def config(m=None):
    return {"name": "tiny-mla", "arch": "deepseek-v2-lite",
            "model": copy.deepcopy(m or model()),
            "weights_dtype": "bfloat16", "reference": "deepseek_v2"}


def batch_cell(limit=0.5, m=None):
    """A replay of three requests decoding side by side, as the
    long-context cell runs."""
    cell = bench_tiny.batch_cell(limit)
    cell.name = "tiny-mla.batch"
    cell.config = config(m)
    return cell
