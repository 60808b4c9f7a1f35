"""Kernels: the latent paged decode kernel's share of its roofline.

The kernel (``paged_decode_attention_latent`` in the trace) computes
multi-head latent attention in its absorbed form: every query head of a
seat scores the same cached row of each live token, [latent ; rope key]
(``kv_lora_rank + qk_rope_head_dim`` lanes), and sums the latent
(``kv_lora_rank`` lanes) under its softmax.  Per seat, per layer, per
tick, for n live tokens, in bf16:

    bytes = 2 n (r + rope)            the rows, read once for all heads
          + 2 H (r + rope)            the latent queries
          + 2 H r                     the outputs
    FLOPs = 2 H n (r + rope) + 2 H n r

The least time is the larger of FLOPs over the bf16 peak and bytes over
the HBM bandwidth, one call per layer per tick, over the kernel's device
time in the trace, in %.  The pool's zero lanes past r + rope (a row is
stored 640 lanes wide) and pages past a seat's length do not count, so
they show as lost roofline.
"""
import tracereduce

KERNEL = r"paged_decode_attention_latent"
BYTES = 2                                   # bf16 pool, queries, outputs


def work(model: dict, live_lengths) -> tuple:
    """(FLOPs, bytes) of one layer's latent decode attention."""
    H, r = model["num_heads"], model["kv_lora_rank"]
    row = r + model["qk_rope_head_dim"]
    flops = nbytes = 0.0
    for n in live_lengths:
        flops += 2.0 * H * n * (row + r)
        nbytes += BYTES * (n * row + H * row + H * r)
    return flops, nbytes


def read(run):
    tw = run.trace_window() if run.trace is not None else None
    if tw is None:
        return None
    spent = tracereduce.op_time_s(run.trace, KERNEL, *tw)
    ticks = [s.decode_lens for s in run.steps if s.decode_lens]
    if spent <= 0 or not ticks:
        return None
    need = 0.0
    for lens in ticks:
        f, b = work(run.model, lens)
        need += max(f / run.peaks["bf16_flops_per_s"],
                    b / run.peaks["hbm_bytes_per_s"]) * run.model["num_layers"]
    return 100.0 * need / spent
