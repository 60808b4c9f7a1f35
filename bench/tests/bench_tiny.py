"""Tiny cells for the benchmark's CPU tests: the harness, the engine and
the reference at a few thousand parameters, with a tied or an untied
output head."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import catalog  # noqa: E402
import weights  # noqa: E402

REF = catalog.load_module(os.path.join(BENCH, "reference",
                                       "dense_decoder.py"),
                          "bench_reference_dense_decoder")

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
         "hbm_bytes": 16e9}


def model(qk_norm=True, kv_heads=2, **over):
    m = {"num_layers": 2, "d_model": 64, "num_heads": 4,
         "num_kv_heads": kv_heads, "head_dim": 16, "d_ff": 128,
         "vocab_size": 256, "qk_norm": qk_norm, "tie_embeddings": True,
         "rope_theta": 10000.0, "norm_eps": 1e-6,
         "compute_dtype": "bfloat16"}
    m.update(over)
    return m


def params(m, seed, dtype):
    """The benchmark's weights for model dict ``m``, as a run makes
    them: the reference's layout drawn from the seed."""
    return weights.make(REF.shapes(m), seed, dtype)


def config(m=None, arch="qwen3-1.7b"):
    return {"name": "tiny", "arch": arch, "model": m or model(),
            "weights_dtype": "bfloat16", "reference": "dense_decoder"}


def metric(name, e2e):
    return catalog.Metric(name, "x", e2e, None)


def _name(kind, tie):
    return f"tiny.{kind}" if tie else f"tiny.{kind}.untied"


def chat_cell(limit=0.5, tie=True):
    traffic = {"kind": "open_loop", "burstiness": 1.0,
               "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.8,
                          "min": 4, "max": 64},
               "output": {"dist": "lognormal", "median": 8, "sigma": 0.7,
                          "min": 2, "max": 32},
               "block": 8}
    settings = {"seats": 4, "max_seq_len": 128, "page_size": 16,
                "prefill_chunk": 32, "num_pages": 64, "rate": 4.0,
                "warmup_s": 1, "resolve_s": 20,
                "check": {"requests": 4, "max_logit_gap": limit}}
    e2e = ["setup_s", "ttft_p90_ms", "tbt_p95_ms"]
    pl = ["queue_wait_p90_ms.chat"]
    return catalog.Cell(_name("chat", tie), 1,
                        config(model(tie_embeddings=tie)), traffic, settings,
                        [metric(n, True) for n in e2e],
                        [metric(n, False) for n in pl])


def batch_cell(limit=0.5, tie=True):
    traffic = {"kind": "replay",
               "requests": [{"prompt": 40, "output": 60},
                            {"prompt": 90, "output": 30},
                            {"prompt": 65, "output": 50}]}
    settings = {"seats": 3, "max_seq_len": 128, "page_size": 16,
                "prefill_chunk": 64, "num_pages": 40,
                "warmup_max_s": 60,
                "check": {"requests": 3, "max_logit_gap": limit}}
    e2e = ["setup_s", "output_tokens_per_s"]
    pl = ["seats_busy_mean.batch", "kv_pages_used_share.batch"]
    return catalog.Cell(_name("batch", tie), 1,
                        config(model(tie_embeddings=tie)), traffic, settings,
                        [metric(n, True) for n in e2e],
                        [metric(n, False) for n in pl])
