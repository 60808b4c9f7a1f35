"""Work the algorithm needs, from shapes alone: the yardstick for
roofline shares and MFU.

These count what the maths of a dense decoder requires, not what an
implementation does: recomputed work, padding, dead pages and layout
copies do not count.  So a faster kernel raises its share, and a
rewrite of the program does not make the count stale.
"""
from __future__ import annotations

from typing import Iterable

KV_BYTES = {"bfloat16": 2, "float32": 4}


def matmul_params(model: dict) -> int:
    """Weights that every decoded token multiplies: the layers'
    projections and the (tied) output head; not the embedding lookup
    nor the norms."""
    L, D = model["num_layers"], model["d_model"]
    H, KVH = model["num_heads"], model["num_kv_heads"]
    hd, F, V = model["head_dim"], model["d_ff"], model["vocab_size"]
    per_layer = D * H * hd + 2 * D * KVH * hd + H * hd * D + 3 * D * F
    return L * per_layer + V * D


def decode_step_flops(model: dict, live_lengths: Iterable[int]) -> float:
    """Model FLOPs of one decode step over the given seats: 2 per
    matmul parameter per seat, plus attention's QK and PV (4 H hd n per
    layer for a seat attending n positions)."""
    lens = list(live_lengths)
    L, H, hd = model["num_layers"], model["num_heads"], model["head_dim"]
    return (2.0 * matmul_params(model) * len(lens)
            + 4.0 * H * hd * L * float(sum(lens)))


def paged_attention_work(model: dict, live_lengths: Iterable[int],
                         kv_dtype: str = "bfloat16",
                         act_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) of one layer's paged decode attention over the
    given seats: each seat reads the K and V of its live tokens and
    writes nothing else back but its output; q and o are ``act_bytes``
    wide."""
    H, KVH, hd = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    kvb = KV_BYTES[kv_dtype]
    flops = 0.0
    nbytes = 0.0
    for n in live_lengths:
        flops += 4.0 * H * hd * n
        nbytes += 2.0 * n * KVH * hd * kvb + 2.0 * H * hd * act_bytes
    return flops, nbytes


def paged_attention_min_s(model: dict, ticks: Iterable[Iterable[int]],
                          peak_flops: float, peak_bw: float,
                          kv_dtype: str = "bfloat16") -> float:
    """Least time the chip could spend in paged decode attention over
    ``ticks`` (each the live lengths of the seats that decoded): per
    call, the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s; one call per layer per tick."""
    total = 0.0
    for lens in ticks:
        f, b = paged_attention_work(model, lens, kv_dtype)
        total += max(f / peak_flops, b / peak_bw) * model["num_layers"]
    return total
