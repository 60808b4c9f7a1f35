"""Model step: model FLOPs of the window's decode ticks over the fused
tick's device time times the chip's bf16 peak, in %, counted for
DeepSeek-V2's block (``bench/work.py`` counts a dense decoder only).

Per decoding seat and tick, 2 FLOPs per weight each token multiplies:

  * attention, every layer: q_proj D x H(nope + rope), kv_a_proj
    D x (r + rope), kv_b_proj r x H(nope + v) (used as W_UK on the
    query and W_UV on the output in the absorbed form), o_proj H v x D;
  * the leading dense layers' MLP: 3 D F;
  * each expert layer: the router D x E, the shared experts 3 D Fs, and
    of the top_k routed experts those held here: top_k * held / E of
    one expert's 3 D Fe on average (6 * 8 / 64 = 0.75);
  * the untied head D x V;

plus the absorbed attention itself, 2 H (r + rope) + 2 H r per live
token per layer.  Padding lanes, dead pages and tokens routed to
experts held elsewhere do not count.
"""
import layers


def seat_flops(model: dict) -> float:
    """Model FLOPs of one decoded token, attention over the cache left
    out."""
    D, H, L = model["d_model"], model["num_heads"], model["num_layers"]
    r, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rope, vd = model["qk_rope_head_dim"], model["v_head_dim"]
    k, m = model["first_k_dense"], model["moe"]
    E, held = m["num_experts"], m.get("experts_held") or m["num_experts"]
    attn = D * H * (nope + rope) + D * (r + rope) + r * H * (nope + vd) \
        + H * vd * D
    dense_mlp = 3 * D * model["d_ff"]
    expert_layer = (D * E + 3 * D * m["d_ff_shared"]
                    + m["top_k"] * held / E * 3 * D * m["d_ff_expert"])
    head = D * model["vocab_size"]
    return 2.0 * (L * attn + k * dense_mlp + (L - k) * expert_layer + head)


def attention_flops(model: dict, n: int) -> float:
    """The absorbed attention of one seat over ``n`` live tokens, all
    layers."""
    H, r = model["num_heads"], model["kv_lora_rank"]
    row = r + model["qk_rope_head_dim"]
    return (2.0 * H * (row + r)) * n * model["num_layers"]


def read(run):
    runs = layers.program_runs(run)
    ticks = [s.decode_lens for s in run.steps if s.decode_lens]
    if runs is None or not ticks:
        return None
    per_seat = seat_flops(run.model)
    flops = sum(per_seat * len(t) + sum(attention_flops(run.model, n)
                                        for n in t) for t in ticks)
    return 100.0 * flops / (layers.seconds(runs[0])
                            * run.peaks["bf16_flops_per_s"])
