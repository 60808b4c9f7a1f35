"""The traffic generator and the catalogue, on the CPU."""
import bench_tiny  # noqa: F401  (paths)

import hashlib
import json
import os
import shutil

import numpy as np
import pytest

import arrivals
import catalog

CHAT = arrivals.load_mix(os.path.join(bench_tiny.BENCH, "traffic",
                                      "chat.json"))
BATCH = arrivals.load_mix(os.path.join(bench_tiny.BENCH, "traffic",
                                       "longctx_batch.json"))


def _schedule(stream, n):
    return [(stream.due_s(i), stream.request(i).prompt.tolist(),
             stream.request(i).max_new_tokens) for i in range(n)]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 40 + 1, -3])
def test_one_seed_one_schedule(seed):
    a = arrivals.Stream(CHAT, seed, 151936, rate=1.3)
    b = arrivals.Stream(CHAT, seed, 151936, rate=1.3)
    assert _schedule(a, 70) == _schedule(b, 70)


def test_seeds_share_the_schedule_and_differ_in_tokens():
    a = arrivals.Stream(CHAT, 11, 1000, rate=2.0)
    b = arrivals.Stream(CHAT, 2 ** 35 + 12, 1000, rate=2.0)
    blk = a.block
    for k in range(3):
        ra = [a.request(i) for i in range(k * blk, (k + 1) * blk)]
        assert sorted(len(r.prompt) for r in ra) == \
            sorted(arrivals._lengths(CHAT["prompt"], blk).tolist())
        assert a.due_s((k + 1) * blk) == pytest.approx((k + 1) * blk / 2.0)
    for i in range(40):
        assert a.due_s(i) == b.due_s(i)
        ra, rb = a.request(i), b.request(i)
        assert (len(ra.prompt), ra.max_new_tokens) == \
            (len(rb.prompt), rb.max_new_tokens)
        assert not np.array_equal(ra.prompt, rb.prompt)
    # the order inside a block is not the sorted one
    assert [len(a.request(i).prompt) for i in range(blk)] != \
        sorted(len(a.request(i).prompt) for i in range(blk))


def test_lengths_keep_to_the_mix():
    s = arrivals.Stream(CHAT, 1, 1000, rate=1.0)
    lens = [len(s.request(i).prompt) for i in range(64)]
    outs = [s.request(i).max_new_tokens for i in range(64)]
    assert min(lens) >= 4 and max(lens) <= 256
    assert min(outs) >= 8 and max(outs) <= 768
    assert max(lens) + max(outs) <= 1024
    # the fit to the source's moments: medians 30 and 141, and means
    # near 69.5 and 214.5 less what the clip takes off the top
    assert 20 < np.median(lens) < 50 and 100 < np.median(outs) < 180
    assert 45 < np.mean(lens) < 70 and 180 < np.mean(outs) < 215
    q = arrivals.Stream(BATCH, 1, 1000)
    assert q.due_s(1) == 0.0
    assert [len(q.request(i).prompt) for i in range(q.size)] == [2432, 3200]
    assert arrivals.longest_total(BATCH) == 3200 + 448
    assert arrivals.longest_total(CHAT) == 256 + 768


def test_open_loop_needs_a_rate_and_kinds_are_checked(tmp_path):
    with pytest.raises(ValueError):
        arrivals.Stream(CHAT, 1, 100)
    bad = dict(CHAT, kind="closed")
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(bad))
    with pytest.raises(ValueError):
        arrivals.load_mix(str(p))


def test_benchmark_names_resolve_to_files():
    cat = catalog.Catalog(bench_tiny.ROOT)
    for w in cat.benchmark["workloads"]:
        cell = cat.cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(m.name == "setup_s" for m in cell.end_to_end)
        arrivals.load_mix(os.path.join(bench_tiny.BENCH, "traffic",
                                       w["traffic"] + ".json"))
        assert cat.reference(cell.config).hidden is not None
    for m in cat.metrics():
        assert callable(cat.reader(m.name))
    for m in cat.benchmark["per_layer"]:
        for w in m["workloads"]:
            cell = cat.cell(w)
            assert m["moves"] in [e.name for e in cell.end_to_end]


def _digest(tree):
    h = {}
    for d, _, files in os.walk(tree):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                h[os.path.relpath(p, tree)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return h


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A later change adds a configuration, a traffic mix, a cell and a
    metric as new files and new entries; nothing that exists changes."""
    root = tmp_path / "checkout"
    bench = root / "bench"
    shutil.copytree(bench_tiny.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(bench_tiny.ROOT, "BENCHMARK.json"), root)
    before = _digest(bench)
    spec = json.loads((root / "BENCHMARK.json").read_text())

    (bench / "configs" / "tiny-new.json").write_text(
        json.dumps(bench_tiny.config()))
    (bench / "traffic" / "bursty.json").write_text(
        json.dumps(dict(CHAT, burstiness=4.0)))
    (bench / "cells" / "tiny-new.bursty.json").write_text(json.dumps(
        {"seats": 2, "max_seq_len": 64, "page_size": 16,
         "prefill_chunk": 16, "num_pages": 16, "rate": 1.0,
         "warmup_s": 1, "check": {"requests": 2}}))
    (bench / "metrics" / "served_requests.bursty.py").write_text(
        "def read(run):\n    return float(len(run))\n")
    spec["configs"].append({"name": "tiny-new", "source": "x",
                            "file": "bench/configs/tiny-new.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "tiny-new.bursty",
                              "config": "tiny-new", "traffic": "bursty",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "served_requests.bursty",
                              "unit": "requests", "better": "higher",
                              "source": "host_clock", "layer": "scheduler",
                              "moves": "ttft_p90_ms",
                              "workloads": ["tiny-new.bursty"]})
    spec["end_to_end"][1]["workloads"].append("tiny-new.bursty")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cat = catalog.Catalog(str(root), bench_dir=str(bench))
    cell = cat.cell("tiny-new.bursty")
    assert cell.traffic["burstiness"] == 4.0
    assert cell.config["model"]["d_model"] == 64
    assert [m.name for m in cell.per_layer] == ["served_requests.bursty"]
    assert "ttft_p90_ms" in [m.name for m in cell.end_to_end]
    assert cat.reader("served_requests.bursty")([1, 2, 3]) == 3.0
    stream = arrivals.Stream(cell.traffic, 1, 100, rate=1.0)
    assert stream.request(0).max_new_tokens >= 8
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_replay_serves_its_list_once_at_the_start():
    """A replay is its list: the lengths as they stand, all due at 0,
    no request past the list, and the seed draws only the tokens."""
    a = arrivals.Stream(BATCH, 3, 1000)
    b = arrivals.Stream(BATCH, 2 ** 33 + 3, 1000)
    want = [(r["prompt"], r["output"]) for r in BATCH["requests"]]
    assert a.size == len(want)
    for i, (p, o) in enumerate(want):
        ra, rb = a.request(i), b.request(i)
        assert (len(ra.prompt), ra.max_new_tokens, ra.due_s) == (p, o, 0.0)
        assert (len(rb.prompt), rb.max_new_tokens) == (p, o)
        assert not np.array_equal(ra.prompt, rb.prompt)
        assert np.array_equal(ra.prompt, arrivals.Stream(
            BATCH, 3, 1000).request(i).prompt)
    with pytest.raises(IndexError):
        a.request(a.size)


@pytest.mark.parametrize("requests", [[], [{"prompt": 0, "output": 4}],
                                      [{"prompt": 8, "output": 0}]])
def test_replay_lists_are_checked(tmp_path, requests):
    p = tmp_path / "replay.json"
    p.write_text(json.dumps({"kind": "replay", "requests": requests}))
    with pytest.raises(ValueError):
        arrivals.load_mix(str(p))
