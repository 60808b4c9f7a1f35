"""Readings from the program's own names: device time under the fused
tick's named scopes, device idle time inside the step's ``serve.*``
phases, and the spans a traced step writes."""
import bench_tiny  # noqa: F401  (paths)

import glob
import os

import numpy as np
import pytest

import harness
import layers
import scopes
import tracereduce
import weights
from test_bench_trace import CHIP_TRACE, _chip_run

RELAYOUT = "jit(_lambda)/while/body/closed_call/paged_attention/kv_relayout"


def _scoped_trace():
    """Two fused-tick runs (a kernel op in each) and a prefill run inside
    the window, a third tick after it; scoped ops in each; a step of
    ``serve.*`` phases around each tick.  Times in ns."""
    ops = [["copy.1", 110, 30], ["paged_decode_attention_pallas.3", 150,
                                 200], ["copy.2", 350, 20],
           ["while.4", 370, 25], ["sort.5", 375, 10],
           ["copy.1", 1010, 20],
           ["copy.1", 2010, 40], ["paged_decode_attention_pallas.3", 2050,
                                  300], ["sort.5", 2360, 30],
           ["copy.1", 5010, 40], ["paged_decode_attention_pallas.3", 5050,
                                  300]]
    scoped = [[RELAYOUT + "/transpose", 110, 30],
              [RELAYOUT + "/transpose", 350, 20],
              ["jit(_lambda)/sample/while", 370, 25],
              ["jit(_lambda)/sample/jit(sort)/sort", 375, 10],
              [RELAYOUT + "/transpose", 1010, 20],     # in the prefill run
              [RELAYOUT + "/transpose", 2010, 40],
              ["jit(_lambda)/sample/jit(sort)/sort", 2360, 30],
              [RELAYOUT + "/transpose", 5010, 40]]     # after the window
    modules = [["jit__lambda", 100, 300], ["jit__lambda", 1000, 100],
               ["jit__lambda", 2000, 400], ["jit__lambda", 5000, 400]]
    host = [["bench.window", 0, 3000],
            ["bench.step", 50, 900], ["serve.admission", 70, 5],
            ["serve.prefill", 75, 15], ["serve.decode", 90, 810],
            ["serve.decode/dispatch", 95, 15], ["fused_decode_tick", 96, 13],
            ["serve.decode/host", 110, 770],
            ["serve.decode/sample", 880, 15], ["serve.bookkeeping", 900, 40],
            ["bench.step", 995, 1500], ["serve.admission", 1000, 5],
            ["serve.prefill", 1005, 95], ["serve.decode", 1100, 1350],
            ["serve.decode/dispatch", 1100, 100],
            ["serve.decode/host", 1200, 1210],
            ["serve.bookkeeping", 2450, 40]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules, "scoped": scoped}],
            "host": host}


def _run(trace, steps=2):
    obs = [harness.StepObs(0, 0, 1, 0, 1, 0, [10], 1, 0)
           for _ in range(steps)]
    return harness.Run("x", {}, {}, {}, bench_tiny.PEAKS, 0.0, 0.0, 1.0,
                       obs, [], 0, 1.0, trace)


def test_scope_time_counts_the_window_ticks_once_each():
    r = _run(_scoped_trace())
    # (30 + 20 + 40) ns over two ticks: the prefill run's op and the
    # tick after the window are left out
    assert scopes.kv_relayout_ms(r) == pytest.approx(45e-6)
    # the loop [370, 395) holds its body's sort: counted once
    assert scopes.sampler_ms(r) == pytest.approx((25 + 30) / 2 * 1e-6)


@pytest.mark.parametrize("dispatch_in_gap", [False, True])
def test_host_idle_counts_gaps_inside_step_phases(dispatch_in_gap):
    t = _scoped_trace()
    # busy [110,140) [150,395) [1010,1030) [2010,2350) [2360,2390): the
    # gaps [140,150) [395,1010) [1030,2010) [2350,2360) fall inside
    # serve.decode/host, [0,110) inside bench.step before its first
    # phase, [2390,3000) after every span
    in_host = 1615e-9
    if dispatch_in_gap:
        # the gap [140,150) goes to the older dispatch span, innermost
        # inside serve.decode/dispatch: still a step phase's idle
        t["host"] += [["serve.decode/dispatch", 141, 8],
                      ["fused_decode_tick", 142, 6]]
        in_host -= 10e-9
    gaps = dict(tracereduce.idle_gaps(t, 0, 3000))
    assert gaps["serve.decode/host"] == pytest.approx(in_host)
    assert gaps.get("fused_decode_tick", 0.0) == pytest.approx(
        1615e-9 - in_host)
    assert gaps["bench.step"] == pytest.approx(110e-9)
    assert gaps["none"] == pytest.approx(610e-9)
    assert scopes.host_idle_ms(_run(t)) == pytest.approx(1615e-6 / 2)


@pytest.mark.parametrize("reader", [scopes.kv_relayout_ms,
                                    scopes.sampler_ms, scopes.host_idle_ms])
def test_readers_read_none_without_the_programs_names(reader):
    """A program without the scopes and spans (or an untraced run)
    gives no reading, not 0."""
    t = _scoped_trace()
    for dev in t["devices"]:
        del dev["scoped"]
    t["host"] = [h for h in t["host"] if not h[0].startswith("serve.")]
    assert reader(_run(t)) is None
    assert reader(_run(None)) is None
    assert reader(_chip_run([3000, 3500])) is None


def test_chip_trace_reads_as_before():
    """The readers that were there read the committed chip trace as they
    did before the scoped ops and step phases came in."""
    t = tracereduce.load(CHIP_TRACE)
    tw = tracereduce.window(t)
    r = _chip_run([3000, 3500])
    assert layers.decode_tick_ms(r) == pytest.approx(321.21986666666663,
                                                     rel=1e-12)
    assert layers.paged_attn_roofline(r) == pytest.approx(
        1.509513319604137, rel=1e-12)
    assert layers.decode_mfu(r) == pytest.approx(0.021009594143331187,
                                                 rel=1e-12)
    assert layers.device_idle_share(r) == pytest.approx(
        0.41304466379372684, rel=1e-12)
    assert tracereduce.idle_gaps(t, *tw) == [
        ["bench.step", pytest.approx(0.003994605999999995, rel=1e-12)],
        ["longest:bench.step", pytest.approx(0.002195068, rel=1e-12)]]
    top = tracereduce.top_ops(t, *tw)
    assert [k for k, _ in top][:3] == ["paged_decode_attention_pallas",
                                       "copy",
                                       "constant_dynamic-update-slice_fusion"]
    assert top[1][1] == pytest.approx(0.23374782599999952, rel=1e-12)


# -- the xplane's event metadata ----------------------------------------------

def _field(num, payload):
    def varint(n):
        out = bytearray()
        while True:
            b = n & 0x7F
            n >>= 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)
    if isinstance(payload, int):
        return varint(num << 3) + varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return varint(num << 3 | 2) + varint(len(payload)) + payload


def _plane(name, ops):
    """An ``XPlane`` whose event metadata names each op with a ``tf_op``
    stat (metadata id 7) and a ``flops`` stat (id 8) beside it."""
    out = _field(2, name)
    for i, (op, tf_op) in enumerate(ops):
        meta = _field(1, i) + _field(2, op) + _field(5, _field(1, 8)
                                                    + _field(3, 12))
        if tf_op is not None:
            meta += _field(5, _field(1, 7) + _field(5, tf_op))
        out += _field(4, _field(1, i) + _field(2, meta))
    for sid, sname in ((7, "tf_op"), (8, "flops")):
        out += _field(5, _field(1, sid) + _field(2, _field(1, sid)
                                                 + _field(2, sname)))
    return out


def test_name_stacks_read_the_tf_op_stat(tmp_path):
    dev = _plane("/device:TPU:0", [
        ("%copy.85 = bf16[8] copy(%a)", RELAYOUT + "/transpose:"),
        ("%fusion.3 = f32[4] fusion(%b)", "jit(_lambda)/while:"),
        ("%sort.8 = f32[4] sort(%c)", "jit(_lambda)/sample/jit(sort)/sort:"),
        ("%copy.112 = bf16[8] copy(%d)", None),
        # one name, two stacks (two programs): left out
        ("%fusion.9 = f32[4] fusion(%e)", "jit(_lambda)/mlp/add:"),
        ("%fusion.9 = f32[4] fusion(%e)", "jit(_lambda)/lm_head/add:")])
    host = _plane("/host:CPU", [("serve.decode", "jit(x)/mlp/add:")])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, dev) + _field(1, host))
    assert scopes.name_stacks(str(path)) == {"/device:TPU:0": {
        "%copy.85 = bf16[8] copy(%a)": RELAYOUT + "/transpose",
        "%sort.8 = f32[4] sort(%c)": "jit(_lambda)/sample/jit(sort)/sort"}}


# -- a traced step ------------------------------------------------------------

def test_a_traced_step_writes_its_phases_in_order(tmp_path):
    """One step that admits, prefills, emits a first token and decodes,
    under the profiler: its ``serve.*`` spans come out of the xplane in
    step order, the sub-phases inside their phase."""
    import jax
    from repro.runtime.serving import PagedServingEngine
    cfg = bench_tiny.config()
    params = weights.make(cfg["model"], 3, cfg["weights_dtype"])
    eng = PagedServingEngine(harness.program_config(cfg), params,
                             page_size=16, num_pages=16, max_seats=2,
                             max_seq_len=64, prefill_chunk=32)
    rng = np.random.default_rng(0)
    for _ in range(2):                   # compile outside the trace
        eng.submit(rng.integers(0, 256, 20).astype(np.int32),
                   max_new_tokens=3)
        eng.run()
    eng.submit(rng.integers(0, 256, 20).astype(np.int32), max_new_tokens=3)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    xplane = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                       recursive=True)
    trace = scopes.from_xplane(xplane[0])
    spans = [h for h in trace["host"] if h[0].startswith("serve.")]
    assert [h[0] for h in spans] == [
        "serve.admission", "serve.prefill", "serve.prefill/first_token",
        "serve.decode", "serve.decode/grow", "serve.decode/sync",
        "serve.decode/dispatch", "serve.decode/host", "serve.decode/sample",
        "serve.bookkeeping"]
    by = {h[0]: (h[1], h[1] + h[2]) for h in spans}
    for name, (s, e) in by.items():
        if "/" in name:
            ps, pe = by[name.split("/")[0]]
            assert ps <= s and e <= pe, name
    tops = sorted((v for k, v in by.items() if "/" not in k))
    assert all(a[1] <= b[0] for a, b in zip(tops, tops[1:]))
    # the older dispatch span, inside its successor
    old = [h for h in trace["host"] if h[0] == "fused_decode_tick"]
    assert len(old) == 1
    s, e = by["serve.decode/dispatch"]
    assert s <= old[0][1] and old[0][1] + old[0][2] <= e
    # what tracereduce gives is kept as it is: scopes only adds
    base = tracereduce.from_xplane(xplane[0])
    assert [h for h in trace["host"] if not h[0].startswith("serve.")] \
        == base["host"]
    assert [{k: v for k, v in d.items() if k != "scoped"}
            for d in trace["devices"]] == base["devices"]
