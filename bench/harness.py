"""One benchmark run of one cell: set-up, a measured window, the trace,
the check against the reference, and the result line.

The system under test is ``repro.runtime.serving.PagedServingEngine``
with its fused decode tick and ``paged_attn_impl="auto"`` (the Pallas
paged decode kernel on a TPU).  The harness drives it the way a server
loop would: it submits each request when it is due and calls
``step()`` while there is work.  It reads the program's seat map and
counters, and times everything on the host's clock.

Order of a run:

 1. device check (a TPU with as many chips as the cell asks for);
 2. persistent compile cache at ``<checkout>/.jax_cache``;
 3. weights from the seed, on the device, in one jitted call, laid out
    by the configuration's reference module;
 4. the cell's engine;
 5. shape warm-up: one request through one prefill chunk and the fused
    tick, so the window compiles nothing;
 6. traffic warm-up: the cell's own arrivals until the seats are steady;
 7. the window: ``seconds`` long, from one step's end to the end of the
    first step that ends after it; with ``trace`` the profiler records
    it;
 8. for an open-loop mix, arrivals go on until every request due in the
    window has its first token (at most ``resolve_s``);
 9. device memory peak, then the engine is freed and the reference
    checks a sample of the served tokens.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

import arrivals
import catalog
import tracereduce
import weights as weights_mod

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The device the cell needs is not there."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Counts executables built (compiled or read from the persistent
    cache) through ``jax.monitoring``.  One per process."""

    _instance = None

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: platform={info['platform']} device_kind="
        f"{info['kind']!r} count={info['count']}")
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"needs a TPU; JAX found {info['platform']!r}")
    if info["count"] < chips:
        raise NoChip(f"needs {chips} chips; JAX found {info['count']}")
    return info


def enable_cache(root: str) -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, for every program however short its compile."""
    import jax
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def peaks_for(kind: str, bench_dir: str = catalog.BENCH_DIR) -> dict:
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def program_config(config: dict):
    """The program's ``ModelConfig``: its registry entry with every size
    the configuration file states put in."""
    from repro.configs import get_config
    return dataclasses.replace(get_config(config["arch"]), **config["model"])


# ---------------------------------------------------------------------------
# observation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    """The benchmark's view of one request."""
    index: int
    due: float                      # host clock
    prompt_len: int
    req: object = None              # the program's Request
    refused: bool = False
    admitted: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)

    @property
    def served(self) -> int:
        return len(self.times)


@dataclasses.dataclass
class StepObs:
    t_start: float
    t_end: float
    seats_busy: int
    pages_in_use: int
    page_capacity: int
    prefill_tokens: int
    decode_lens: List[int]          # live length of each seat that decoded
    tokens: int                     # tokens the host received in this step
    queued: int                     # requests waiting for a seat after it
    cpu_s: float = 0.0              # this thread's CPU time in ``step()``
    proc_cpu_s: float = 0.0         # all the process's threads' CPU time
    gc_s: float = 0.0               # collector pauses in ``step()``


@dataclasses.dataclass
class Run:
    """What a run measured: the metric readers' only input."""
    workload: str
    model: dict
    settings: dict
    traffic: dict
    peaks: dict
    setup_s: float
    t0: float
    t1: float
    steps: List[StepObs]            # the window's steps
    records: List[Record]
    compiles_in_window: int
    resolved_at: float              # end of the open loop's last wait
    trace: Optional[dict] = None    # normalised, see tracereduce

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def trace_window(self):
        return None if self.trace is None else tracereduce.window(self.trace)


class GcPauses:
    """Times the cyclic garbage collector's pauses (``gc.callbacks``)."""

    def __init__(self):
        self.seconds = 0.0
        self.pauses: List[tuple] = []       # (generation, start, seconds)
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            d = time.perf_counter() - self._t
            self.seconds += d
            self.pauses.append((info["generation"], self._t, d))

    def close(self):
        gc.callbacks.remove(self._on)


class Driver:
    """Feeds one engine from one request stream and records what the
    host receives."""

    def __init__(self, eng, stream: arrivals.Stream, clock=time.perf_counter):
        self.eng = eng
        self.stream = stream
        self.clock = clock
        self.gc = GcPauses()
        self.records: List[Record] = []
        self.live: Dict[int, Record] = {}       # rid -> record, not done
        self.queued: Dict[int, Record] = {}     # rid -> record, not seated
        self.steps: List[StepObs] = []
        self.start = 0.0
        self.next = 0

    def submit(self, i: int, due: float) -> None:
        r = self.stream.request(i)
        rec = Record(i, due, len(r.prompt))
        try:
            rid = self.eng.submit(r.prompt, max_new_tokens=r.max_new_tokens)
        except ValueError as e:
            rec.refused = True
            log(f"request {i} refused: {e}")
        else:
            rec.req = self.eng.queue[-1]
            assert rec.req.rid == rid
            self.live[rid] = rec
            self.queued[rid] = rec
        self.records.append(rec)

    def submit_due(self, now: float) -> None:
        while self.start + self.stream.due_s(self.next) <= now:
            self.submit(self.next, self.start + self.stream.due_s(self.next))
            self.next += 1

    def submit_all(self, now: float) -> None:
        """Replay mix: every request of the list, once, at the start."""
        while self.next < self.stream.size:
            self.submit(self.next, now)
            self.next += 1

    def step(self) -> StepObs:
        eng = self.eng
        m = eng.metrics
        pre0 = m.prefill_tokens
        gc0, cpu0 = self.gc.seconds, time.thread_time()
        proc0 = time.process_time()
        t_start = self.clock()
        import jax
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
        t_end = self.clock()
        cpu, proc = time.thread_time() - cpu0, time.process_time() - proc0
        gcs = self.gc.seconds - gc0
        with jax.profiler.TraceAnnotation("bench.observe"):
            obs = self._observe(t_start, t_end, m.prefill_tokens - pre0)
        obs.cpu_s, obs.proc_cpu_s, obs.gc_s = cpu, proc, gcs
        self.steps.append(obs)
        return obs

    def _observe(self, t_start, t_end, prefill_tokens) -> StepObs:
        eng = self.eng
        for rid in [r for r, rec in self.queued.items()
                    if rec.req.slot is not None or rec.req.done]:
            self.queued.pop(rid).admitted = t_start
        decode_lens, tokens = [], 0
        for rid in list(self.live):
            rec = self.live[rid]
            req = rec.req
            n = len(req.generated)
            new = n - len(rec.times)
            if new > 0:
                tokens += new
                rec.times.extend([t_end] * new)
                if n > 1:
                    # this tick's input sat at position prompt + n - 2,
                    # so the seat attended prompt + n - 1 positions
                    decode_lens.append(rec.prompt_len + n - 1)
            if req.done:
                del self.live[rid]
        return StepObs(t_start, t_end, len(eng.seats),
                       eng.metrics.pages_in_use, eng.bm.capacity,
                       prefill_tokens, decode_lens, tokens, len(eng.queue))

    def busy(self) -> bool:
        return bool(self.eng.queue or self.eng.seats)


def warm_shapes(eng, settings: dict, vocab: int, seed: int) -> None:
    """Compile every shape the window uses: one request whose prompt
    spans one full prefill chunk and a part of a second, then decodes
    a few tokens through the fused tick; then one whose prompt starts
    with the first's first tokens, so the prefix cache copies a
    partly matching page (traffic without shared prefixes still meets
    such a match when two prompts happen to open with the same token)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed % (1 << 64), 9]))
    n = min(settings["prefill_chunk"] + 1,
            settings["max_seq_len"] - 4)
    first = rng.integers(0, vocab, n, dtype=np.int32)
    second = np.concatenate([first[:2], rng.integers(0, vocab, n - 2,
                                                     dtype=np.int32)])
    for prompt in (first, second):
        eng.submit(prompt, max_new_tokens=3)
        while eng.queue or eng.seats:
            eng.step()
    import jax
    jax.block_until_ready(eng.cache)


def measure(eng, cell: catalog.Cell, seed: int, seconds: float,
            trace_dir: Optional[str], t_process: float, peaks: dict,
            counter: CompileCounter) -> Run:
    """Steps 6-8 of a run (see the module docstring)."""
    import jax
    settings, mix = cell.settings, cell.traffic
    vocab = cell.config["model"]["vocab_size"]
    stream = arrivals.Stream(mix, seed, vocab, settings.get("rate"))
    drv = Driver(eng, stream)
    open_loop = mix["kind"] == "open_loop"

    def feed(now):
        if open_loop:
            drv.submit_due(now)
        else:
            drv.submit_all(now)

    def advance():
        """One step if there is work, else wait for the next arrival."""
        now = drv.clock()
        with jax.profiler.TraceAnnotation("bench.submit"):
            feed(now)
        if drv.busy():
            return drv.step()
        nxt = drv.start + stream.due_s(drv.next)
        time.sleep(max(0.0, min(0.005, nxt - drv.clock())))
        return None

    # -- traffic warm-up -----------------------------------------------------
    drv.start = drv.clock()
    if open_loop:
        until = drv.start + float(settings["warmup_s"])
        while drv.clock() < until:
            advance()
    else:
        limit = drv.start + float(settings.get("warmup_max_s", 600))
        while True:
            advance()
            # a replay: every request seated and through its prefill
            full = not eng.queue and len(eng.seats) == stream.size and all(
                r.prefill_pos >= len(r.prefill_src)
                for r in eng.seats.values())
            if full:
                break
            if drv.clock() > limit:
                raise RuntimeError("warm-up did not seat the replay")
    warm_steps = len(drv.steps)

    # -- the window ------------------------------------------------------------
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    c0 = counter.count
    t0 = drv.clock()
    setup_s = t0 - t_process
    with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
        while True:
            obs = advance()
            now = obs.t_end if obs is not None else drv.clock()
            if now >= t0 + seconds:
                break
    t1 = now
    compiles = counter.count - c0
    trace = None
    if trace_dir is not None:
        jax.profiler.stop_trace()
    window_steps = [s for s in drv.steps[warm_steps:] if s.t_end <= t1]

    # -- open loop: every request due in the window gets its first token ------
    if open_loop:
        limit = t1 + float(settings.get("resolve_s", 60))
        pending = lambda: [r for r in drv.records
                           if t0 <= r.due < t1 and not r.refused
                           and not r.times]
        while pending() and drv.clock() < limit:
            advance()
    drv.gc.close()

    if trace_dir is not None:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if files:
            xplane = max(files, key=os.path.getmtime)
            trace = tracereduce.from_xplane(xplane)
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window: {t1 - t0:.3f} s, {len(window_steps)} steps, "
        f"{sum(s.tokens for s in window_steps)} tokens, "
        f"{compiles} compilations inside the window")
    log_host(window_steps, drv.gc, t0, t1)
    return Run(cell.name, cell.config["model"], settings, mix, peaks,
               setup_s, t0, t1, window_steps, drv.records, compiles,
               drv.clock(), trace)


def log_host(steps: List[StepObs], pauses: GcPauses, t0: float,
             t1: float) -> None:
    """Where the host's time went in the window: the longest steps, with
    the CPU time of this thread and of the whole process, and the
    collector's pauses, inside each.  A step that is long with neither
    CPU nor collector time in it waited on something outside the
    process."""
    if not steps:
        return
    walls = sorted(s.t_end - s.t_start for s in steps)
    log(f"host: step median {walls[len(walls) // 2] * 1e3:.1f} ms, "
        f"CPU in steps {sum(s.cpu_s for s in steps):.3f} s")
    for s in sorted(steps, key=lambda s: s.t_start - s.t_end)[:3]:
        log(f"host: step at +{s.t_start - t0:.3f} s took "
            f"{(s.t_end - s.t_start) * 1e3:.1f} ms, CPU: thread "
            f"{s.cpu_s * 1e3:.1f} ms, process {s.proc_cpu_s * 1e3:.1f} ms; "
            f"collector {s.gc_s * 1e3:.1f} ms")
    inside = [p for p in pauses.pauses if t0 <= p[1] < t1]
    full = [p for p in inside if p[0] == 2]
    log(f"host: collector in the window: {len(inside)} pauses, "
        f"{sum(p[2] for p in inside):.3f} s; {len(full)} full, longest "
        f"{max((p[2] for p in full), default=0.0) * 1e3:.1f} ms; "
        f"longest full pause in the run "
        f"{max((p[2] for p in pauses.pauses if p[0] == 2), default=0.0) * 1e3:.1f} ms")


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def verdict(check: dict, reading: Optional[float]) -> tuple:
    """The comparison that decides ``correct``: the widest logit gap
    against the cell's limit.  Returns ``(correct, numbers)``; the
    program's run and the control go through this one function."""
    limit = float(check["max_logit_gap"]) if "max_logit_gap" in check \
        else None
    ok = reading is not None and limit is not None and reading <= limit
    return ok, {"max_logit_gap": {"value": reading, "limit": limit}}


def pick_sample(run: Run, k: int, seed: int) -> List[Record]:
    """``k`` served requests drawn from the seed, with the one that
    received most tokens among them; finished requests first."""
    served = [r for r in run.records if r.req is not None and r.served]
    done = [r for r in served if r.req.done]
    pool = done if len(done) >= k else served
    pool = sorted(pool, key=lambda r: r.index)
    if not pool:
        return []
    longest = max(pool, key=lambda r: (r.served, -r.index))
    rest = [r for r in pool if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed % (1 << 64), 5]))
    pick = [rest[i] for i in sorted(rng.choice(len(rest),
                                               min(k - 1, len(rest)),
                                               replace=False))]
    return [longest] + pick


def served_gaps(ref, params, model: dict, sample: List[Record],
                rows: int, length: int, control: bool = False) -> dict:
    """For each served token of ``sample``: how far its logit lies
    below the reference's best at that position.  With ``control``,
    also the same for the token the control (the reference in fp8)
    puts first.  ``rows`` x ``length`` is the fixed padded batch.
    ``ref`` is the configuration's reference module: it takes from
    ``model`` what its maths needs and picks its own output head from
    ``params``."""
    import jax.numpy as jnp
    tokens = np.zeros((rows, length), np.int32)
    where_b, where_t, targets = [], [], []
    for b, rec in enumerate(sample):
        req = rec.req
        out = np.asarray(req.generated[:rec.served], np.int32)
        seq = np.concatenate([req.prompt, out[:-1]])
        tokens[b, :len(seq)] = seq
        P = len(req.prompt)
        for i, tok in enumerate(out):
            where_b.append(b)
            where_t.append(P - 1 + i)
            targets.append(int(tok))
    n = len(targets)
    R = max(ref.HEAD_BLOCK, 1 << (n - 1).bit_length())
    pad = R - n
    wb = np.asarray(where_b + [0] * pad)
    wt = np.asarray(where_t + [0] * pad)
    tg = jnp.asarray(np.asarray(targets + [0] * pad, np.int32))
    h = ref.hidden(params, jnp.asarray(tokens), model, fp8=False)
    rows_ref = h[wb, wt]
    best, got = ref.head_stats(params, rows_ref, tg)
    gaps = np.asarray(best - got)[:n]
    out = {"tokens": n, "max_gap": float(gaps.max()),
           "mean_gap": float(gaps.mean())}
    if control:
        del h
        hc = ref.hidden(params, jnp.asarray(tokens), model, fp8=True)
        pick = ref.head_argmax_fp8(params, hc[wb, wt])
        del hc
        best, got = ref.head_stats(params, rows_ref, pick)
        cg = np.asarray(best - got)[:n]
        out["control_max_gap"] = float(cg.max())
        out["control_mean_gap"] = float(cg.mean())
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _free(eng) -> None:
    """Drop the engine's device state (pool, tick state) before the
    reference runs; the weights stay, the reference reads them."""
    pol = eng.policy
    pol.cache = None
    pol._dev = None
    pol._prefill_row = None
    eng.seats.clear()
    eng.queue.clear()
    gc.collect()


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        *, t_process: float, require_tpu: bool = True,
        control: bool = False, cell: Optional[catalog.Cell] = None,
        cache: bool = True, peaks: Optional[dict] = None) -> dict:
    """One run; returns ``{"line": the result line, "run": Run,
    "gaps": the check's readings}``, and with ``control`` also
    ``"control"``: the control's ``correct`` and numbers, from the same
    comparison as the line's.  The keywords after ``trace`` serve
    the calibration, the sweep and the tests: ``control`` adds the
    control's readings, ``cell`` replaces the catalogue's cell,
    ``cache=False`` leaves the persistent compile cache off and
    ``peaks`` replaces the table's row (a CPU has none)."""
    cat = catalog.Catalog(root)
    cell = cell or cat.cell(workload)
    device = device_info(cell.chips, require_tpu)
    cache_dir = enable_cache(root) if cache else None
    counter = CompileCounter.get()
    import jax
    from repro.runtime.serving import PagedServingEngine
    from repro.runtime.telemetry import Telemetry
    peaks = peaks if peaks is not None else peaks_for(device["kind"])
    log(f"{workload}: seed {seed}, {seconds} s, trace {int(trace)}, "
        f"compile cache {cache_dir}")

    s = cell.settings
    model = cell.config["model"]
    ref = cat.reference(cell.config)
    params = weights_mod.make(ref.shapes(model), seed,
                              cell.config["weights_dtype"])
    eng = PagedServingEngine(
        program_config(cell.config), params, page_size=s["page_size"],
        num_pages=s["num_pages"], max_seats=s["seats"],
        max_seq_len=s["max_seq_len"], prefill_chunk=s["prefill_chunk"],
        telemetry=Telemetry(profile=True) if trace else None)
    warm_shapes(eng, s, model["vocab_size"], seed)
    log(f"shapes warm: {counter.count} executables built "
        f"({counter.seconds:.1f} s) by "
        f"{time.perf_counter() - t_process:.1f} s")

    trace_dir = os.path.join(root, ".bench_out", f"trace-{workload}") \
        if trace else None
    r = measure(eng, cell, seed, seconds, trace_dir, t_process, peaks,
                counter)
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    # the check, once the window has closed and the engine is freed
    chk = s["check"]
    sample = pick_sample(r, int(chk["requests"]), seed)
    eng_refused = sum(1 for x in r.records if x.refused)
    _free(eng)
    t_chk = time.perf_counter()
    gaps = served_gaps(ref, params, model, sample, int(chk["requests"]),
                       s["max_seq_len"], control=control) if sample else None
    log(f"check: {len(sample)} requests, "
        f"{gaps['tokens'] if gaps else 0} served tokens, "
        f"{time.perf_counter() - t_chk:.1f} s")

    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        v = cat.reader(m.name)(r)
        if v is None:
            if m.end_to_end:
                raise RuntimeError(f"end-to-end metric {m.name} has no value")
            continue
        metrics[m.name] = {"value": float(v), "unit": m.unit}

    due = [x for x in r.records if r.t0 <= x.due < r.t1]
    in_window = {id(x) for x in due} | {
        id(x) for x in r.records if any(r.t0 < t <= r.t1 for t in x.times)}
    failed = eng_refused
    if cell.traffic["kind"] == "open_loop":
        failed += sum(1 for x in due if not x.refused and not x.times)
    ok, check = verdict(chk, gaps["max_gap"] if gaps else None)
    dev = dict(device, memory_peak_bytes=peak)
    line = {"correct": bool(ok), "attempted": len(in_window),
            "failed": failed, "metrics": metrics, "device": dev}
    if trace:
        tw = r.trace_window()
        if tw is not None:
            busy = tracereduce.busy_s(r.trace, *tw)
            dev["busy_s"] = busy
            dev["window_s"] = (tw[1] - tw[0]) / 1e9
            line["breakdown"] = {
                "device_ops": tracereduce.top_ops(r.trace, *tw),
                "idle_gaps": tracereduce.idle_gaps(r.trace, *tw)}
            os.makedirs(os.path.join(root, ".bench_out"), exist_ok=True)
            tracereduce.save(r.trace, os.path.join(
                root, ".bench_out", f"{workload}-{seed}.trace.json.gz"))
    line["check"] = check
    out = {"line": line, "run": r, "gaps": gaps}
    if control:
        c_ok, c_check = verdict(chk, gaps.get("control_max_gap")
                                if gaps else None)
        out["control"] = {"correct": c_ok, "check": c_check}
    # the weights and the engine's jitted programs go before the next
    # run in this process (the calibration and the sweep make several)
    del eng, params
    gc.collect()
    return out
