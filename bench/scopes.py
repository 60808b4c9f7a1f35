"""Readings from the program's own names in a profiler trace: device time
under a named scope of the fused decode tick (``SCOPES``), and device
idle time while the host was inside one of the serving step's
``serve.*`` phases.

``tracereduce.from_xplane`` keeps neither: its host spans are the
benchmark's ``bench.*`` and ``tracereduce.PROGRAM_SPANS``, and its ops
carry only their HLO names.  ``from_xplane`` here normalises the same
file to the same dict and adds both:

    {"devices": [{..., "scoped": [[stack, start_ns, dur_ns], ...]}],
     "host":    [..., ["serve.<phase>", start_ns, dur_ns], ...]}

``scoped`` lists again each op of ``ops`` whose JAX name stack (the HLO
``op_name``, which a TPU xplane keeps as the ``tf_op`` stat of the op's
event metadata) holds one of ``SCOPES``.  The readers take a
``harness.Run`` whose trace was normalised so; a trace of a program
without the names, or one normalised by ``tracereduce`` alone, gives
None, not 0.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple

import layers
import tracereduce

#: the program's named scopes on the device work of a serving step
SCOPES = ("kv_write", "kv_relayout", "paged_attention", "attn_proj", "mlp",
          "lm_head", "sample")
#: the prefix of the serving step's phase spans
STEP_PHASE = "serve."


def _varint(b, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b) -> Iterable[Tuple[int, object]]:
    """(field number, value) of each field of one protobuf message:
    an int for varints, a memoryview for the rest."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def name_stacks(path: str) -> Dict[str, Dict[str, str]]:
    """``{device plane: {op event name: JAX name stack}}`` read from the
    xplane's event metadata, for ops whose stack holds one of ``SCOPES``.
    ``ProfileData`` does not show event metadata, so this walks the
    protobuf (``XSpace.planes`` 1; ``XPlane`` name 2, event_metadata 4,
    stat_metadata 5; ``XEventMetadata`` name 2, stats 5; ``XStat``
    metadata_id 1, str_value 5).  A name that two programs give to ops
    of different stacks is left out."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    scopes = set(SCOPES)
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(dict(_fields(v)).get(2, b""))
            elif g == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        tf_op = [k for k, n in stat_names.items() if n == "tf_op"]
        if not tf_op:
            continue
        stacks: Dict[str, Optional[str]] = {}
        for ev in events:
            op, stack = None, None
            for g, v in _fields(ev):
                if g == 2:
                    op = bytes(v).decode()
                elif g == 5:
                    st = dict(_fields(v))
                    if st.get(1) == tf_op[0] and 5 in st:
                        # "<name stack>:<op type>"
                        stack = bytes(st[5]).decode().rsplit(":", 1)[0]
            if op is None or stack is None or \
                    scopes.isdisjoint(stack.split("/")):
                continue
            stacks[op] = stack if stacks.get(op, stack) == stack else None
        out[name] = {k: v for k, v in stacks.items() if v is not None}
    return out


def from_xplane(path: str) -> dict:
    """``tracereduce.from_xplane(path)`` with each device's ``scoped``
    ops and the host's ``serve.*`` spans added; what that gives is kept
    as it is."""
    from jax.profiler import ProfileData
    trace = tracereduce.from_xplane(path)
    stacks = name_stacks(path)
    pd = ProfileData.from_file(path)
    devices = {d["name"]: d for d in trace["devices"]}
    for plane in pd.planes:
        if plane.name in devices:
            scoped = stacks.get(plane.name, {})
            devices[plane.name]["scoped"] = [
                [scoped[e.name], float(e.start_ns), float(e.duration_ns)]
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events if e.name in scoped]
        elif plane.name.startswith("/host:"):
            trace["host"] += [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for line in plane.lines for e in line.events
                if e.name.startswith(STEP_PHASE)]
    trace["host"].sort(key=lambda s: s[1])
    return trace


def _in_runs(starts, runs, s: float) -> bool:
    i = tracereduce._bisect(starts, s + 1) - 1
    return i >= 0 and s < runs[i][1] + runs[i][2]


def scope_ms_per_tick(run, scope: str) -> Optional[float]:
    """Device ms of the ops under ``scope`` (a component of their name
    stack) inside the window's fused-tick runs, per run.  Ops that nest
    (a loop and its body) count once: their intervals are merged."""
    runs = layers.program_runs(run)
    if runs is None:
        return None
    ticks = sorted(runs[0], key=lambda m: m[1])
    starts = [m[1] for m in ticks]
    found, ns = False, 0.0
    for dev in run.trace["devices"]:
        ivs = [(s, s + d) for stack, s, d in dev.get("scoped", ())
               if scope in stack.split("/") and _in_runs(starts, ticks, s)]
        found = found or bool(ivs)
        ns += sum(b - a for a, b in tracereduce.merge(ivs))
    if not found:
        return None
    return ns / len(ticks) / 1e6


def kv_relayout_ms(run):
    """Kernels: the paged kernel's wrapper relaying the KV pool, scales
    and page table out into the kernel's layout (``kv_relayout``), ms
    per fused tick."""
    return scope_ms_per_tick(run, "kv_relayout")


def sampler_ms(run):
    """Model step: the fused tick's on-device sampler (``sample``), ms per
    fused tick."""
    return scope_ms_per_tick(run, "sample")


def _program_span(name: str) -> bool:
    # ``fused_decode_tick`` lies inside ``serve.decode/dispatch``
    return name.startswith(STEP_PHASE) or name in tracereduce.PROGRAM_SPANS


def host_idle_ms(run):
    """Scheduler: device idle ms per window step in gaps whose midpoint
    falls inside a ``serve.*`` step phase (the innermost kept host span
    around it, as ``tracereduce.idle_gaps`` attributes them)."""
    tw = run.trace_window() if run.trace is not None else None
    if tw is None or not run.steps or not any(
            h[0].startswith(STEP_PHASE) for h in run.trace["host"]):
        return None
    gaps = tracereduce.idle_gaps(run.trace, *tw,
                                 n=len(run.trace["host"]) + 2)
    idle = sum(sec for span, sec in gaps if _program_span(span))
    return idle / len(run.steps) * 1e3
