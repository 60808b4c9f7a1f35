"""Trace reduction, work counts and the peaks table."""
import bench_tiny  # noqa: F401  (paths)

import os

import pytest

import harness
import layers
import tracereduce
import work

#: three fused decode ticks of minicpm-2b.longctx_batch (2 seats, 40
#: layers), recorded on one TPU v5e and normalised by ``from_xplane``
CHIP_TRACE = os.path.join(bench_tiny.BENCH, "tests", "data",
                          "minicpm-2b.longctx_batch.3ticks.trace.json.gz")
MINICPM = {"num_layers": 40, "d_model": 2304, "num_heads": 36,
           "num_kv_heads": 36, "head_dim": 64, "d_ff": 5760,
           "vocab_size": 122753}
QWEN3 = {"num_layers": 28, "d_model": 2048, "num_heads": 16,
         "num_kv_heads": 8, "head_dim": 128, "d_ff": 6144,
         "vocab_size": 151936}


def _synthetic():
    """One device: two decode ticks (a kernel op inside each) and one
    prefill run; host spans around them; times in ns."""
    ops = [["fusion.1", 100, 50], ["paged_decode_attention_pallas.3", 150,
                                   200], ["fusion.2", 340, 60],
           ["fusion.1", 1000, 100],
           ["fusion.1", 2000, 40], ["paged_decode_attention_pallas.3", 2050,
                                    300], ["fusion.4", 2300, 100]]
    modules = [["jit__lambda_", 100, 300], ["jit__lambda_", 1000, 100],
               ["jit_argmax", 1150, 10], ["jit__lambda_", 2000, 400]]
    host = [["bench.window", 0, 3000], ["bench.step", 50, 900],
            ["fused_decode_tick", 60, 20], ["bench.observe", 950, 40],
            ["bench.step", 995, 1500]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "host": host}


def test_busy_is_the_union_of_op_intervals():
    t = _synthetic()
    assert tracereduce.window(t) == (0, 3000)
    # [100, 400) [1000, 1100) [2000, 2040) [2050, 2400): overlaps
    # count once
    assert tracereduce.busy_s(t, 0, 3000) == pytest.approx(790e-9)
    assert tracereduce.busy_s(t, 200, 1050) == pytest.approx(250e-9)
    assert tracereduce.merge([(5, 9), (1, 3), (2, 4), (9, 10)]) == \
        [[1, 4], [5, 10]]


def test_kernel_time_and_program_runs_by_name():
    t = _synthetic()
    assert tracereduce.op_time_s(t, layers.KERNEL, 0, 3000) == \
        pytest.approx(500e-9)
    holding, rest = tracereduce.modules_holding(t, layers.KERNEL, 0, 3000)
    assert [m[1] for m in holding] == [100, 2000]
    assert [m[1] for m in rest] == [1000, 1150]


def test_idle_gaps_go_to_the_host_span_around_them():
    t = _synthetic()
    gaps = dict(tracereduce.idle_gaps(t, 0, 3000))
    # [0,100) and [400,1000) fall in the first bench.step's span (by
    # their midpoints), [1100,2000) and [2040,2050) in the second;
    # [2400,3000) ends the window outside every span
    assert gaps["bench.step"] == pytest.approx((100 + 600 + 900 + 10)
                                               * 1e-9)
    assert gaps["none"] == pytest.approx(600e-9)
    assert gaps["longest:bench.step"] == pytest.approx(900e-9)
    top = tracereduce.top_ops(t, 0, 3000)
    assert top[0] == ["paged_decode_attention_pallas", pytest.approx(5e-7)]


def test_work_counts_live_tokens_only():
    assert work.matmul_params(QWEN3) == 28 * (2 * 2048 * 16 * 128
                                             + 2 * 2048 * 8 * 128
                                             + 3 * 2048 * 6144) \
        + 151936 * 2048
    f, b = work.paged_attention_work(QWEN3, [100, 300])
    assert f == 4 * 16 * 128 * 400
    assert b == 2 * 400 * 8 * 128 * 2 + 2 * (2 * 16 * 128 * 2)
    # bytes bound on a v5e: one call per layer
    t = work.paged_attention_min_s(QWEN3, [[100, 300]], 197e12, 819e9)
    assert t == pytest.approx(28 * b / 819e9)
    fl = work.decode_step_flops(QWEN3, [100, 300])
    assert fl == 2 * work.matmul_params(QWEN3) * 2 + 4 * 16 * 128 * 28 * 400


def test_peaks_by_device_kind():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")


def _chip_run(live):
    """A Run over the recorded trace whose three window steps each
    decoded seats of the given live lengths."""
    t = tracereduce.load(CHIP_TRACE)
    steps = [harness.StepObs(0, 0, len(live), 0, 1, 0, list(live),
                             len(live), 0) for _ in range(3)]
    return harness.Run("minicpm-2b.longctx_batch", MINICPM, {}, {},
                       bench_tiny.PEAKS, 0.0, 0.0, 1.0, steps, [], 0, 1.0,
                       t)


def test_chip_trace_busy_kernel_and_ticks():
    t = tracereduce.load(CHIP_TRACE)
    t0, t1 = tracereduce.window(t)
    ops = t["devices"][0]["ops"]
    # busy is the union of op intervals: check against a plain sweep
    # over the sorted interval ends
    edges = sorted((max(s, t0), min(s + d, t1)) for _, s, d in ops
                   if s < t1 and s + d > t0)
    union, reach = 0.0, t0
    for a, b in edges:
        if b > reach:
            union += b - max(a, reach)
            reach = b
    busy = tracereduce.busy_s(t, t0, t1)
    assert busy == pytest.approx(union / 1e9)
    assert 0 < busy <= (t1 - t0) / 1e9
    # one kernel call per layer per tick, found by name
    assert sum(1 for n, s, d in ops if "paged_decode_attention" in n
               and s < t1 and s + d > t0) == 3 * 40
    kernel = tracereduce.op_time_s(t, layers.KERNEL, t0, t1)
    decode, prefill = layers.program_runs(_chip_run([3000, 3500]))
    assert len(decode) == 3 and prefill == []
    assert 0 < kernel < layers.seconds(decode)
    idle = layers.device_idle_share(_chip_run([3000]))
    assert idle == pytest.approx(100 * (1 - busy / ((t1 - t0) / 1e9)))


def test_chip_trace_roofline_and_mfu_follow_live_lengths():
    t = tracereduce.load(CHIP_TRACE)
    kernel = tracereduce.op_time_s(t, layers.KERNEL,
                                   *tracereduce.window(t))
    live = [3000, 3500]
    share = layers.paged_attn_roofline(_chip_run(live))
    # bytes bound: K and V of the live tokens plus q and o, per layer
    need = 3 * 40 * sum(2 * n * 36 * 64 * 2 + 2 * 36 * 64 * 2
                        for n in live) / 819e9
    assert share == pytest.approx(100 * need / kernel)
    assert 0 < share < 100
    # twice the live tokens, about twice the share: dead pages and
    # padding the kernel walks do not count
    assert layers.paged_attn_roofline(_chip_run([6000, 7000])) == \
        pytest.approx(2 * share, rel=1e-3)
    mfu = layers.decode_mfu(_chip_run(live))
    decode, _ = layers.program_runs(_chip_run(live))
    flops = 3 * work.decode_step_flops(MINICPM, live)
    assert mfu == pytest.approx(100 * flops / (layers.seconds(decode)
                                               * 197e12))
    assert 0 < mfu < 100
