"""Reduction of a profiler trace to the numbers the metric readers use.

A trace is first normalised (``from_xplane``) to a small JSON-able dict,
which is what the readers and the tests see:

    {"devices": [{"name": "/device:TPU:0",
                  "ops":     [[name, start_ns, dur_ns], ...],   # XLA Ops
                  "modules": [[name, start_ns, dur_ns], ...]}], # XLA Modules
     "host":    [[name, start_ns, dur_ns], ...]}               # kept spans

All times are on the profiler's one clock, in nanoseconds.  Host spans
kept are the benchmark's own (``bench.*``) and the program's annotations
in ``PROGRAM_SPANS``.  ``bench.window`` marks the measured window.
"""
from __future__ import annotations

import gzip
import json
import re
from typing import Dict, Iterable, List, Optional, Tuple

#: annotations the program itself writes into the profiler's trace
PROGRAM_SPANS = ("fused_decode_tick",)
WINDOW_SPAN = "bench.window"


def _keep_host(name: str) -> bool:
    return name.startswith("bench.") or name in PROGRAM_SPANS


def op_name(event_name: str) -> str:
    """A TPU trace names each op by its whole HLO instruction
    (``%copy.116 = bf16[...] copy(%x)``): keep the instruction's own
    name, so that an op is not matched by the operands it reads."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def from_xplane(path: str) -> dict:
    """Normalise the ``.xplane.pb`` file at ``path``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                dev[key] = [[op_name(e.name), float(e.start_ns),
                             float(e.duration_ns)] for e in line.events]
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if _keep_host(e.name):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns)])
    host.sort(key=lambda s: s[1])
    return {"devices": devices, "host": host}


def save(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def window(trace: dict) -> Optional[Tuple[float, float]]:
    """(start_ns, end_ns) of the measured window's span."""
    spans = [s for s in trace["host"] if s[0] == WINDOW_SPAN]
    if not spans:
        return None
    s = spans[-1]
    return s[1], s[1] + s[2]


def merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Union of [start, end) intervals, sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(iv: List[List[float]], t0: float, t1: float) -> List[List[float]]:
    return [[max(a, t0), min(b, t1)] for a, b in iv if b > t0 and a < t1]


def busy_intervals(dev: dict, t0: float, t1: float) -> List[List[float]]:
    return _clip(merge((s, s + d) for _, s, d in dev["ops"]), t0, t1)


def busy_s(trace: dict, t0: float, t1: float) -> Optional[float]:
    """Seconds in [t0, t1) in which some operation ran, averaged over
    the devices the trace holds."""
    devs = [d for d in trace["devices"] if d["ops"]]
    if not devs:
        return None
    total = sum(b - a for d in devs for a, b in busy_intervals(d, t0, t1))
    return total / len(devs) / 1e9


def _overlaps(s: float, d: float, t0: float, t1: float) -> bool:
    """Whether [s, s + d) meets [t0, t1).  The device's clock and the
    host's differ by about a millisecond in a TPU trace (the first
    program the window dispatches can read as starting before the
    window's span does), so work is assigned to the window by overlap,
    never by its start alone."""
    return s < t1 and s + d > t0


def op_time_s(trace: dict, pattern: str, t0: float, t1: float) -> float:
    """Device seconds of ops whose name matches ``pattern``, summed
    over ops and devices, for ops that overlap [t0, t1)."""
    rx = re.compile(pattern)
    return sum(d for dev in trace["devices"] for n, s, d in dev["ops"]
               if _overlaps(s, d, t0, t1) and rx.search(n)) / 1e9


def modules_holding(trace: dict, pattern: str, t0: float,
                    t1: float) -> Tuple[List[list], List[list]]:
    """Split the module runs that overlap [t0, t1) into those holding
    an op that matches ``pattern`` and the rest."""
    rx = re.compile(pattern)
    holding, rest = [], []
    for dev in trace["devices"]:
        marks = sorted(s for n, s, _ in dev["ops"] if rx.search(n))
        for m in dev["modules"]:
            name, s, d = m
            if not _overlaps(s, d, t0, t1):
                continue
            lo = _bisect(marks, s)
            (holding if lo < len(marks) and marks[lo] < s + d
             else rest).append(m)
    return holding, rest


def _bisect(xs: List[float], x: float) -> int:
    lo, hi = 0, len(xs)
    while lo < hi:
        mid = (lo + hi) // 2
        if xs[mid] < x:
            lo = mid + 1
        else:
            hi = mid
    return lo


def leaf_ops(dev: dict, t0: float, t1: float) -> List[list]:
    """Ops overlapping [t0, t1) that hold no other op: a loop
    (``while``) is listed beside the ops of its body and would count
    them twice."""
    ops = sorted((o for o in dev["ops"] if _overlaps(o[1], o[2], t0, t1)),
                 key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= o[1] + o[2]
            or nxt[1] + nxt[2] > o[1] + o[2]]


def top_ops(trace: dict, t0: float, t1: float, n: int = 10) -> List[list]:
    """The ``n`` op names with most device seconds over [t0, t1), the
    numbers in each name (HLO instance suffixes) folded away; ops that
    hold others are left out (see ``leaf_ops``)."""
    tot: Dict[str, float] = {}
    for dev in trace["devices"]:
        for name, s, d in leaf_ops(dev, t0, t1):
            key = re.sub(r"\.\d+$", "", name)
            tot[key] = tot.get(key, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, t0: float, t1: float, n: int = 10) -> List[list]:
    """Device idle time in [t0, t1) by what the host was doing: each gap
    between busy intervals (of the first device) goes to the innermost
    kept host span that covers its midpoint, else to ``"none"``.
    Returns the ``n`` largest [span, seconds] totals, and the longest
    single gap under the key ``"longest:<span>"``."""
    devs = [d for d in trace["devices"] if d["ops"]]
    if not devs:
        return []
    busy = busy_intervals(devs[0], t0, t1)
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    spans = sorted((s for s in trace["host"] if s[0] != WINDOW_SPAN),
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    tot: Dict[str, float] = {}
    longest = ("none", 0.0)
    for a, b in gaps:
        mid = (a + b) / 2
        best = None
        # spans nest shallowly: the covering ones start among the last
        # few before the midpoint
        hi = _bisect(starts, mid + 1)
        for name, s, d in spans[max(0, hi - 64):hi]:
            if s <= mid < s + d and (best is None or d < best[1]):
                best = (name, d)
        key = best[0] if best else "none"
        tot[key] = tot.get(key, 0.0) + (b - a) / 1e9
        if (b - a) / 1e9 > longest[1]:
            longest = (key, (b - a) / 1e9)
    out = [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])]
    out = out[: n - 1]
    out.append([f"longest:{longest[0]}", longest[1]])
    return out
