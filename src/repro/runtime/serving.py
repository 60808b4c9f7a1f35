"""Unified continuous-batching serving stack (vLLM-style, JAX-native).

One :class:`Scheduler` runs every arch; a pluggable *KV placement policy*
supplies the cache layout and the model arithmetic, and a shared
``runtime.sampler.Sampler`` turns logits into tokens for both::

            submit(prompt, sampling) ─────► FCFS queue
                                               │
                 ┌─────────────────────────────┘
                 ▼
            Scheduler.step()                      (one engine tick)
              1. admission  — policy.try_admit(): reserve a seat and
                 KV placement (fixed slot | pages + cached-prefix refs)
              2. policy.prefill_tick()  — prompt K/V into placement
              3. policy.decode_tick()   — one token per ready seat
                 │ per-seat logits row
                 ▼
            Sampler.sample(logits, req.sampling, rid, step)
                 │ next token id (greedy argmax when temperature=0)
                 ▼
            Scheduler bookkeeping — trace, EngineMetrics, eos/max-new
            completion, finish() → policy.release() returns the KV

    placement policies
      FixedSlotPolicy  — B dense cache slots of max_len tokens each;
                         whole-prompt prefill scattered into the slot.
                         Covers the archs with fixed-size per-request
                         state (SSM, encoder/decoder, vision/audio
                         frontends) and is the equivalence oracle for
                         the paged path.
      PagedPolicy      — KV in a shared pool of fixed-size pages
                         (``runtime.paged_kv.BlockManager``), chunked
                         prefill interleaved with decode, gather-over-
                         page-table attention (``attention.paged_
                         attention``; Pallas kernel on TPU), and
                         refcounted prefix caching: admission points the
                         leading page-table entries of a request whose
                         prompt starts with an already-cached page-
                         aligned token run at those physical pages
                         (refcount++), copy-on-writes only the last
                         partially matching page, and skips prefilling
                         everything cached.  Refcount-0 cached pages
                         park in an LRU list and are evicted under
                         pressure.

    lazy growth + preemption (PagedPolicy, ``lazy_pages=True`` default)
      Admission reserves only the *prompt's* pages (plus cached-prefix
      refs) instead of ``ceil((prompt + max_new) / page_size)`` up
      front; ``decode_tick`` calls ``BlockManager.try_grow`` for one
      page whenever a request's next write crosses a page boundary.  A
      low-watermark admission gate (``watermark`` fraction of capacity,
      ≥1 page, waived when the pool is idle) keeps headroom so live
      requests usually grow unopposed.  When growth still fails the
      Scheduler *preempts the youngest decoding request*: its pages are
      freed (full prompt pages stay in the prefix index, so re-admission
      recomputes them through the prefix-hit path), its generated tokens
      are kept, and it returns to the queue head; on re-admission it
      re-prefills ``prompt + generated[:-1]`` and re-enters decode by
      feeding ``generated[-1]`` — token streams are exactly preserved
      (the sampler is deterministic per (seed, rid, step)).

:class:`ServingEngine` (fixed-slot) and :class:`PagedServingEngine` are
thin façades binding the Scheduler to one policy; both complete requests
on max_new_tokens or eos and ``run`` raises :class:`SchedulerStallError`
when ticks run out with work still pending (stalls fail loudly).

    SLO classes (admission + preemption)
      Every request carries a ``priority`` class — ``premium`` >
      ``standard`` > ``batch`` — and an optional TTFT deadline
      (``deadline_ms``).  Admission is a pluggable policy:
      :class:`FCFSAdmission` (the default — byte-for-byte the historical
      strict-FCFS behavior) or :class:`SLOAdmission`, which admits by
      (effective class, earliest deadline, submit order) with an aging
      bound: a queued request gains one effective class per
      ``aging_ticks`` ticks waited, unclamped, so ``batch`` always
      eventually outranks a stream of fresh ``premium`` arrivals.
      Preemption victim selection is priority-aware under *every*
      admission policy: lowest class first, youngest (highest rid)
      within a class, and a grower never preempts a strictly
      higher-class request on its own behalf (it evicts itself
      instead).  With uniform priorities this degenerates to the
      historical youngest-first rule, so default traces are unchanged.

Scheduling is deterministic (FCFS admission, lowest-rid prefill first,
seats scanned in index order, priority-aware youngest-first preemption)
so trace tests can assert exact interleavings.  ``trace`` records
(tick, event, rid) tuples with events: admit / prefix_hit /
prefill_chunk / first_token / decode / preempt / deadline_miss /
tbt_miss / finish.

Every ``_trace`` site also feeds the optional structured telemetry
plane (``telemetry=`` a :class:`~repro.runtime.telemetry.Telemetry`):
the same events — plus a telemetry-only ``submit`` — land in a bounded
ring-buffer flight recorder as :class:`~repro.runtime.telemetry.
TraceEvent` records carrying injected-clock wall time, the engine id
and small attrs, exportable as Perfetto span timelines and dumped with
a full engine-state snapshot when ``run`` stalls.  With the default
``telemetry=None`` the hot path pays one attribute load + None check
per event (benchmark workload 9 gates the on/off throughput ratio).
Each step phase (admission, prefill and its first-token pull, the
decode tick's grow / sync / dispatch / host / sample, bookkeeping) is
also a ``serve.<phase>`` span on the profiler's clock, so a
``jax.profiler`` trace puts every device idle gap beside the host work
around it.  See ``docs/observability.md``.

See ``docs/serving.md`` for the end-to-end architecture guide (tick
loop, page lifecycle, prefix-cache CoW, lazy growth, preemption replay,
SLO classes) and ``docs/benchmarks.md`` for how the serving benchmarks
measure this stack.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mixed_precision
from repro.models import model as M
from repro.parallel.sharding import LogicalRules, SINGLE_DEVICE_RULES
from repro.runtime.paged_kv import BlockManager, EngineMetrics, PrefixMatch
from repro.runtime.sampler import GREEDY, Sampler, SamplingParams


class SchedulerStallError(RuntimeError):
    """``run`` exhausted ``max_ticks`` with requests still queued/active.

    The message names every stalled request as ``rid(priority)`` so
    starvation and deadline bugs are debuggable straight from the
    exception (and the trace): a stall whose stragglers are all
    ``batch`` under an aggressive aging bound reads very differently
    from one whose ``premium`` head is blocked on pages."""


#: Priority classes, best first.  Lower level = higher priority; the
#: admission and preemption orderings compare these levels, never the
#: class names.
PRIORITIES: Dict[str, int] = {"premium": 0, "standard": 1, "batch": 2}

DEFAULT_PRIORITY = "standard"


class _TimedPhase:
    """A step phase's span with its wall time added to a
    :class:`~repro.runtime.telemetry.TickProfiler`: wall time,
    deliberately not the injected clock, which is virtual under the load
    harness and would time every phase as 0."""

    __slots__ = ("_name", "_prof", "_span", "_t0")

    def __init__(self, prof, name: str):
        self._name = name
        self._prof = prof
        self._span = jax.profiler.TraceAnnotation("serve." + name)

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        self._prof.add(self._name, time.perf_counter() - self._t0)
        self._span.__exit__(*exc)


def priority_level(req: "Request") -> int:
    """Numeric level of ``req``'s priority class (0 = most urgent)."""
    return PRIORITIES[req.priority]


class FCFSAdmission:
    """Strict first-come-first-served admission (the default).

    Always proposes the queue head and nothing else; if the head cannot
    be placed, admission stops for the tick (no skip-ahead — a convoy
    of small requests cannot starve a large head).  This is exactly the
    pre-SLO Scheduler behavior: with it, traces are bit-identical to
    engines built before admission became pluggable."""

    name = "fcfs"

    def select(self, sched: "Scheduler") -> Optional["Request"]:
        """Return the next admission candidate or None when the queue
        is empty.  The Scheduler stops admitting for the tick when the
        returned candidate cannot be placed."""
        return sched.queue[0] if sched.queue else None


class SLOAdmission:
    """Priority + earliest-deadline-first admission with aging.

    Candidates are ranked by ``(effective class, absolute TTFT
    deadline, rid)``:

    - *effective class* is the request's priority level minus one for
      every ``aging_ticks`` ticks it has waited in the queue (time
      spent decoding on a seat never counts: preemption restarts the
      aging base at the preemption tick).  The boost is unclamped, so
      any request — ``batch`` included — eventually outranks an
      endless stream of fresh ``premium`` arrivals: the starvation
      bound is ``(level_gap + 1) * aging_ticks`` ticks of queue wait.
    - within a class, requests sort earliest *effective deadline*
      first (EDF).  The effective deadline is the earlier of the TTFT
      deadline (``t_submit + deadline_ms``) and — for TBT-deadlined
      requests — the next-token due time (last emission, or submit
      when nothing is emitted yet, plus ``tbt_deadline_ms``): a
      preempted decode-deadline request re-queues with the urgency of
      its *next* token, and a fresh TBT-deadlined request carries its
      first-token urgency from submit.  Requests with neither
      deadline sort after all deadlined peers;
    - remaining ties fall back to submit order (rid), i.e. FCFS — a
      uniform-priority, no-deadline workload admits in exactly the
      FCFS order.

    Like FCFS, admission is strict head-of-line over this ordering:
    when the best-ranked candidate cannot be placed, nothing else is
    admitted this tick (skipping ahead would hand the pages the head
    is waiting for to lower-ranked work)."""

    name = "slo"

    def __init__(self, aging_ticks: int = 64):
        if aging_ticks < 1:
            raise ValueError(f"aging_ticks must be >= 1, got {aging_ticks}")
        self.aging_ticks = aging_ticks

    def rank(self, req: "Request", tick: int) -> Tuple[int, float, int]:
        """Admission key for ``req`` at scheduler ``tick`` (lower is
        admitted first): (aged priority level, effective absolute
        deadline seconds or +inf, rid).  The effective deadline is the
        earlier of the TTFT deadline and the TBT next-token due time
        (see the class docstring)."""
        waited = max(0, tick - req.submit_tick)
        eff = priority_level(req) - waited // self.aging_ticks
        deadline = (req.t_submit + req.deadline_ms / 1e3
                    if req.deadline_ms is not None else math.inf)
        tbt_ms = getattr(req, "tbt_deadline_ms", None)  # stub-tolerant
        if tbt_ms is not None:
            base = (req.t_last_token if req.t_last_token is not None
                    else req.t_submit)
            deadline = min(deadline, base + tbt_ms / 1e3)
        return (eff, deadline, req.rid)

    def select(self, sched: "Scheduler") -> Optional["Request"]:
        """Best-ranked queued request for this tick, or None.

        Single manual pass with :meth:`rank`'s key computation inlined:
        this scan is O(queue) per free seat per tick and dominates an
        overloaded engine's host time (the load harness drives queues
        thousands deep), where ``min(queue, key=...)`` pays a Python
        frame per element.  Must order identically to
        ``min(queue, key=lambda r: self.rank(r, tick))``."""
        queue = sched.queue
        if not queue:
            return None
        tick, aging = sched._tick, self.aging_ticks
        levels = PRIORITIES
        best = None
        best_key: Tuple[int, float, int] = (0, 0.0, 0)
        for req in queue:
            waited = tick - req.submit_tick
            eff = levels[req.priority] - (waited if waited > 0 else 0) // aging
            deadline = (req.t_submit + req.deadline_ms / 1e3
                        if req.deadline_ms is not None else math.inf)
            tbt_ms = req.tbt_deadline_ms
            if tbt_ms is not None:
                due = (req.t_last_token if req.t_last_token is not None
                       else req.t_submit) + tbt_ms / 1e3
                if due < deadline:
                    deadline = due
            key = (eff, deadline, req.rid)
            if best is None or key < best_key:
                best, best_key = req, key
        return best


def _make_admission(admission, aging_ticks: int):
    """Resolve an admission spec — ``"fcfs"``, ``"slo"`` or a policy
    object with ``select(scheduler)`` — into a policy instance."""
    if isinstance(admission, str):
        if admission == "fcfs":
            return FCFSAdmission()
        if admission == "slo":
            return SLOAdmission(aging_ticks)
        raise ValueError(f"unknown admission policy {admission!r}; "
                         "expected 'fcfs' or 'slo'")
    if not hasattr(admission, "select"):
        raise TypeError(f"admission policy {admission!r} has no select()")
    return admission


@dataclasses.dataclass
class Request:
    """One serving request and its scheduler-owned lifecycle state.

    Constructor-facing fields (set via :meth:`Scheduler.submit`):
      rid: engine-assigned id, monotonically increasing in submit order.
      prompt: (P,) int32 token ids.
      max_new_tokens: generation budget (the eos token counts toward it).
      eos_id: stop decoding early when this token is produced.
      sampling: per-request :class:`SamplingParams` (greedy by default).
      priority: SLO class name — one of :data:`PRIORITIES`.
      deadline_ms: optional TTFT deadline, milliseconds from submit;
          drives EDF ordering under :class:`SLOAdmission` and the
          deadline-miss metric/trace event under every policy.
      tbt_deadline_ms: optional per-token TBT (time-between-tokens)
          decode deadline, milliseconds between consecutive emitted
          tokens.  Under :class:`SLOAdmission` the request's *next*
          token due time (last emission + TBT budget) joins the EDF
          key, so a preempted TBT-deadlined request re-admits with
          real urgency; :meth:`Scheduler.pick_victim` never prefers a
          TBT-deadlined request as a preemption victim while a
          same-or-lower-class victim without one exists; TBT misses
          are counted per class under every policy.
    The remaining fields are filled in by the engine as the request
    moves through admit → prefill → decode → finish (or preempt)."""
    rid: int
    prompt: np.ndarray              # (P,) int32
    max_new_tokens: int
    eos_id: Optional[int] = None
    sampling: SamplingParams = GREEDY
    priority: str = DEFAULT_PRIORITY
    deadline_ms: Optional[float] = None
    tbt_deadline_ms: Optional[float] = None
    # filled by the engine:
    generated: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None      # seat index (paged) / cache slot (fixed)
    pages: List[int] = dataclasses.field(default_factory=list)
    prefill_pos: int = 0            # prompt tokens already placed (paged)
    cached_tokens: int = 0          # prompt tokens served by the prefix cache
    registered_pages: int = 0       # prompt pages published to the prefix index
    match_version: Optional[int] = None  # BlockManager.version at last failed
    #                                      admission attempt (re-match gate)
    resume_tokens: Optional[np.ndarray] = None  # replay prefill source after
    #                                             a preemption (prompt +
    #                                             generated[:-1])
    times_preempted: int = 0
    done: bool = False
    submit_tick: int = 0            # scheduler tick at submit (aging base)
    t_submit: float = 0.0
    t_first_token: Optional[float] = None
    t_last_token: Optional[float] = None  # latest emission (TBT base)
    t_done: Optional[float] = None

    @property
    def prefill_src(self) -> np.ndarray:
        """Tokens the policy must (re)prefill: the prompt, or — after a
        preemption — the prompt plus all generated tokens but the last
        (the last one re-enters through the normal decode feed, so the
        replayed KV and sampling steps line up exactly with an
        uncontended run)."""
        return self.prompt if self.resume_tokens is None else self.resume_tokens


class Scheduler:
    """Engine-agnostic serving loop: queue, seats, admission, sampling,
    completion, metrics and trace.  All KV placement and model calls live
    in the bound policy (see module docstring)."""

    default_max_ticks = 100_000

    def __init__(self, policy, *, max_seats: int,
                 sampler: Optional[Sampler] = None, page_capacity: int = 0,
                 admission="fcfs", aging_ticks: int = 64,
                 clock=None, record_trace: bool = True, telemetry=None):
        """Bind ``policy`` (the KV placement + model arithmetic) to a
        fresh scheduler.

        Args:
          policy: placement policy (:class:`FixedSlotPolicy` or
              :class:`PagedPolicy`); ``policy.bind(self)`` is called.
          max_seats: concurrent-request limit (seat indices
              ``0..max_seats-1``).
          sampler: shared :class:`~repro.runtime.sampler.Sampler`;
              a default stateless one is built when None.
          page_capacity: usable KV pages, threaded into
              :class:`EngineMetrics` for utilization reporting (0 for
              pageless policies).
          admission: ``"fcfs"`` (default, historical behavior),
              ``"slo"`` (priority + EDF + aging) or a policy object
              with ``select(scheduler) -> Optional[Request]``.
          aging_ticks: SLO anti-starvation bound — a queued request
              gains one effective priority class per this many ticks
              waited.  Ignored by FCFS.
          clock: zero-arg callable returning monotonic seconds; every
              timestamp the engine records (submit, TTFT, TBT,
              completion, metric windows) reads it.  None (default) =
              ``time.perf_counter`` — wall time, the serving behavior.
              The load harness injects a
              :class:`~repro.runtime.workload.VirtualClock` so
              deadline verdicts and throughput are deterministic
              functions of the schedule, not of host speed.
          record_trace: keep the per-event ``trace`` list (default).
              ``False`` sets ``trace = None`` and skips every append —
              at 10⁵⁻⁶-request harness scale the trace would dominate
              memory.
          telemetry: optional
              :class:`~repro.runtime.telemetry.Telemetry` — every
              ``_trace`` site then also emits a structured
              :class:`~repro.runtime.telemetry.TraceEvent` (injected-
              clock time, ``engine_id``, attrs) into its flight
              recorder, deadlined TTFT/TBT verdicts feed its SLO
              burn-rate monitor, ``run`` dumps a postmortem through it
              on a stall, and its tick profiler (when enabled) times
              the step phases.  None (default) keeps the hot path at
              one attribute load + None check per event.

        Raises:
          ValueError: unknown ``admission`` name or ``aging_ticks < 1``.
          TypeError: ``admission`` object without a ``select`` method.
        """
        self.policy = policy
        self.max_seats = max_seats
        self.sampler = sampler or Sampler()
        self.admission = _make_admission(admission, aging_ticks)
        self.clock = clock if clock is not None else time.perf_counter
        self.seats: Dict[int, Request] = {}             # seat -> request
        self.queue: Deque[Request] = deque()
        self.finished: List[Request] = []
        self.metrics = EngineMetrics(page_capacity=page_capacity)
        self.trace: Optional[List[Tuple[int, str, int]]] = (
            [] if record_trace else None)
        self.telemetry = telemetry
        self.engine_id = ""       # the fleet labels replicas "model/i"
        self._next_rid = 0
        self._tick = 0
        policy.bind(self)

    def _trace(self, event: str, rid: int,
               attrs: Optional[dict] = None) -> None:
        """Append one (tick, event, rid) trace tuple — no-op when the
        trace is disabled (``record_trace=False``) — and mirror the
        event into the telemetry flight recorder when one is attached.
        ``attrs`` never reaches the flat trace (parity tests pin its
        exact tuples); hot callers pass None so the off path allocates
        nothing."""
        if self.trace is not None:
            self.trace.append((self._tick, event, rid))
        tel = self.telemetry
        if tel is not None:
            tel.emit(self._tick, self.clock(), self.engine_id, rid,
                     event, attrs)

    # -- queue ---------------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               sampling: Optional[SamplingParams] = None,
               priority: str = DEFAULT_PRIORITY,
               deadline_ms: Optional[float] = None,
               tbt_deadline_ms: Optional[float] = None,
               rid: Optional[int] = None) -> int:
        """Queue one request; returns its engine-assigned rid.

        Args:
          prompt: 1-D int32 token ids (non-empty).
          max_new_tokens: generation budget, >= 1.
          eos_id: optional early-stop token id.
          sampling: per-request :class:`SamplingParams` (greedy when
              None).  The sampler keys its streams by (seed, rid,
              step) only — priority never changes tokens.
          priority: SLO class, one of :data:`PRIORITIES`
              (``premium``/``standard``/``batch``).
          deadline_ms: optional TTFT deadline in milliseconds from now
              (must be > 0): EDF ordering under ``slo`` admission and
              deadline-miss accounting under every policy.
          tbt_deadline_ms: optional per-token decode deadline in
              milliseconds (must be > 0): each decode token is due
              this long after the previous emission.  Folds into the
              ``slo`` EDF key (the next-token due time competes with
              the TTFT deadline), shields the request in
              :meth:`pick_victim`, and drives per-class TBT-miss
              accounting under every policy.
          rid: explicit request id (fleet routing — the
              :class:`~repro.runtime.router.ModelFleet` assigns rids
              from one fleet-global counter so sampler keys
              ``(seed, rid, step)`` never collide across engines and a
              routed request replays bit-identically on a solo engine
              given the same rid).  Must keep this engine's rids
              strictly increasing; None (default) auto-assigns.

        Raises:
          ValueError: unknown priority, non-positive deadline, a
              non-monotonic explicit ``rid``, or a prompt/budget the
              bound policy cannot ever place (empty prompt,
              ``prompt + max_new_tokens`` over the engine's length
              bound, or an infeasible page demand).
        """
        if priority not in PRIORITIES:
            raise ValueError(f"unknown priority {priority!r}; expected one "
                             f"of {sorted(PRIORITIES)}")
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        if tbt_deadline_ms is not None and tbt_deadline_ms <= 0:
            raise ValueError(
                f"tbt_deadline_ms must be > 0, got {tbt_deadline_ms}")
        if rid is None:
            rid = self._next_rid
        elif rid < self._next_rid:
            # rid order is load-bearing: prefill picks the lowest rid,
            # preemption the highest, SLO ties break on rid — an engine's
            # rids must stay strictly increasing in submit order
            raise ValueError(
                f"explicit rid {rid} is not monotonic: this engine has "
                f"already assigned rids up to {self._next_rid - 1}")
        req = Request(rid, np.asarray(prompt, np.int32),
                      max_new_tokens, eos_id, sampling or GREEDY,
                      priority=priority, deadline_ms=deadline_ms,
                      tbt_deadline_ms=tbt_deadline_ms,
                      submit_tick=self._tick, t_submit=self.clock())
        self.policy.validate(req)
        self._next_rid = rid + 1
        self.queue.append(req)
        self.metrics.submitted += 1
        tel = self.telemetry
        if tel is not None:
            # telemetry-only event: the flat trace's exact tuple
            # sequence is pinned by parity tests and starts at admit
            tel.emit(self._tick, req.t_submit, self.engine_id, req.rid,
                     "submit", {"priority": req.priority,
                                "prompt_tokens": int(req.prompt.size),
                                "max_new_tokens": req.max_new_tokens})
        return req.rid

    def _free_seats(self) -> List[int]:
        return [s for s in range(self.max_seats) if s not in self.seats]

    def _admit_from_queue(self):
        """Admit while the admission policy's candidate has a seat AND
        a placement (preemption-free at admission time — an admitted
        request can always start; shortfall queues, never crashes).
        The candidate is the queue head under FCFS, the best
        (class, deadline, rid) rank under SLO; either way admission is
        strict head-of-line: the first unplaceable candidate ends the
        tick's admissions."""
        for seat in self._free_seats():
            req = self.admission.select(self)
            if req is None:
                break
            if not self.policy.try_admit(req, seat):
                break
            self.queue.remove(req)
            req.slot = seat
            self.seats[seat] = req
            self.metrics.admitted += 1
            self._trace("admit", req.rid,
                        None if self.telemetry is None else
                        {"seat": seat, "priority": req.priority,
                         "cached_tokens": req.cached_tokens,
                         "preempted_before": req.times_preempted})
            if req.cached_tokens:
                self.metrics.cached_prompt_tokens += req.cached_tokens
                self._trace("prefix_hit", req.rid)

    # -- token bookkeeping ----------------------------------------------------

    def _emit_first_tokens(self, ready: List[Tuple[Request, object]]) -> None:
        """Sample the TTFT token for every request whose prefill
        completed this tick, in ONE batched sampler call with ONE
        device→host transfer, then timestamp all of them *after* the
        batch — a burst of K admissions used to serialize K blocking
        per-request argmax pulls, inflating every later request's
        recorded TTFT (and its deadline-miss verdict) with its
        predecessors' sync time.

        ready: ``[(req, logits_row)]`` in emission (rid) order, each
        row the last prompt position's ``(V,)`` logits."""
        if not ready:
            return
        with self._phase("prefill/first_token"):
            if all(isinstance(row, np.ndarray) for _, row in ready):
                # oracle-policy path: rows never left the host, so a jnp
                # round-trip would only add dispatch latency at harness
                # scale — same argmax/Sampler algebra on numpy arrays
                host = np.stack([row for _, row in ready])
                if all(req.sampling.greedy for req, _ in ready):
                    toks = np.argmax(host, axis=-1).astype(np.int32)
                else:
                    toks = [self.sampler.sample(host[i], req.sampling,
                                                rid=req.rid, step=0)
                            for i, (req, _) in enumerate(ready)]
            else:
                rows = jnp.stack([row for _, row in ready])
                if all(req.sampling.greedy for req, _ in ready):
                    # one on-device argmax over the burst; K ints cross
                    # to host
                    toks = np.asarray(  # repro-lint: disable=RL001
                        jnp.argmax(rows, axis=-1), np.int32)
                else:
                    # one (K, V) transfer for the whole burst
                    host = np.asarray(rows)  # repro-lint: disable=RL001
                    toks = [self.sampler.sample(host[i], req.sampling,
                                                rid=req.rid, step=0)
                            for i, (req, _) in enumerate(ready)]
        now = self.clock()
        for (req, _), tok in zip(ready, toks):
            tok = int(tok)
            req.generated.append(tok)
            req.t_first_token = now
            req.t_last_token = now
            ttft = now - req.t_submit
            missed = (req.deadline_ms is not None
                      and ttft * 1e3 > req.deadline_ms)
            self.metrics.note_first_token(
                req.priority, ttft, deadlined=req.deadline_ms is not None,
                missed=missed)
            tel = self.telemetry
            self._trace("first_token", req.rid,
                        None if tel is None else {"ttft_s": ttft})
            if tel is not None and req.deadline_ms is not None:
                tel.observe_slo(now, self._tick, self.engine_id,
                                req.priority, "ttft", missed)
            if missed:
                self._trace("deadline_miss", req.rid,
                            None if tel is None else
                            {"ttft_s": ttft,
                             "deadline_ms": req.deadline_ms})
            hit_eos = req.eos_id is not None and tok == req.eos_id
            if req.max_new_tokens <= 1 or hit_eos:
                self.finish(req)

    def _sample_decode_batch(self, last_logits, seat_ids) -> Dict[int, int]:
        """Next token per seat from ``(max_seats, V)`` device logits —
        the fallback per-tick path (fixed-slot archs and ``fused=False``
        paged engines; the fused paged path samples on device inside
        ``fused_decode_tick`` instead).  Only the *active* seats' rows
        are gathered — idle seats' logits are never reduced or
        transferred: greedy-only batches move K ints to the host, and
        the (K, V) active rows cross only when a stochastic seat needs
        them."""
        sel = last_logits[jnp.asarray(seat_ids, jnp.int32)]
        if all(self.seats[s].sampling.greedy for s in seat_ids):
            # the batch's one transfer: K ints, post-argmax
            toks = np.asarray(  # repro-lint: disable=RL001
                jnp.argmax(sel, axis=-1), np.int32)
            return {s: int(toks[i]) for i, s in enumerate(seat_ids)}
        # active rows only — never the full (max_seats, V) matrix
        rows = np.asarray(sel)  # repro-lint: disable=RL001
        return {s: self.sampler.sample(rows[i], self.seats[s].sampling,
                                       rid=self.seats[s].rid,
                                       step=len(self.seats[s].generated))
                for i, s in enumerate(seat_ids)}

    def _emit_decode_token(self, req: Request, tok: int) -> None:
        req.generated.append(tok)
        self.metrics.decode_tokens += 1
        now = self.clock()
        # TBT = gap since the previous emission (first token included
        # as the base); preemption replay gaps land here by design —
        # that is exactly the stall a TBT deadline is meant to expose
        tbt = now - req.t_last_token
        req.t_last_token = now
        deadlined = req.tbt_deadline_ms is not None
        missed = deadlined and tbt * 1e3 > req.tbt_deadline_ms
        self.metrics.note_decode_token(req.priority, tbt,
                                       deadlined=deadlined, missed=missed)
        self._trace("decode", req.rid)
        tel = self.telemetry
        if tel is not None and deadlined:
            tel.observe_slo(now, self._tick, self.engine_id,
                            req.priority, "tbt", missed)
        if missed:
            self._trace("tbt_miss", req.rid,
                        None if tel is None else
                        {"tbt_s": tbt,
                         "tbt_deadline_ms": req.tbt_deadline_ms})
        hit_eos = req.eos_id is not None and tok == req.eos_id
        if len(req.generated) >= req.max_new_tokens or hit_eos:
            self.finish(req)

    def finish(self, req: Request) -> None:
        """Complete ``req``: the policy releases its KV placement, the
        seat frees, and per-engine + per-class completion counters and
        the ``finish`` trace event are recorded."""
        req.done = True
        req.t_done = self.clock()
        self.policy.release(req)
        del self.seats[req.slot]
        self.finished.append(req)
        self.metrics.note_completion(req.priority)
        self._trace("finish", req.rid)

    def preempt(self, req: Request) -> None:
        """Evict a decoding request under memory pressure: the policy
        frees its placement (``policy.preempt`` also stashes the replay
        source), generated-so-far tokens are kept, and the request
        returns to the queue *head* — re-admission re-prefills
        ``prompt + generated``, cheap when the prefix index still holds
        the prompt pages.  (Under SLO admission the requeue position is
        cosmetic: ordering is recomputed from class/deadline/rid every
        tick.)

        Raises:
          ValueError: ``req`` has not emitted its first token yet (a
              mid-prefill request has no tokens to replay)."""
        if not req.generated:
            raise ValueError(
                f"cannot preempt request {req.rid} before its first "
                "token; only decoding requests are preemptible (a "
                "mid-prefill request has no tokens to replay)")
        self.policy.preempt(req)
        del self.seats[req.slot]
        req.slot = None
        self.queue.appendleft(req)
        # aging measures queue wait, not lifetime: restart the aging
        # base at the preemption tick so time spent decoding on a seat
        # cannot boost a preempted batch request past fresh premium
        # arrivals (FCFS ignores submit_tick entirely)
        req.submit_tick = self._tick
        req.times_preempted += 1
        self.metrics.note_preemption(req.priority)
        self._trace("preempt", req.rid)

    def pick_victim(self, victims: List[Request],
                    grower: Request) -> Request:
        """Priority-aware preemption victim among ``victims`` (all
        decoding) on behalf of ``grower``: the lowest class goes first;
        within a class, requests *without* a TBT decode deadline are
        preferred over TBT-deadlined ones (evicting a
        decode-deadline-critical request guarantees a TBT miss on its
        replay, so it is never the preferred victim while any
        same-or-lower-class alternative exists); youngest (highest
        rid) breaks the remaining ties — and a grower never preempts a
        strictly higher class than its own; when only higher-class
        victims exist it evicts itself.  With uniform priorities and
        no TBT deadlines anywhere this is bit-identical to the
        historical youngest-first rule (the middle key is constant).

        When ``grower`` is itself in ``victims`` (as in
        ``PagedPolicy._grow_tick``), the ``max`` alone already yields
        self-eviction — the grower outranks any strictly higher class
        in this ordering — so the explicit guard below exists for
        callers passing a victim set that *excludes* the grower, where
        it enforces the never-preempt-upward contract."""
        victim = max(victims, key=lambda r: (
            priority_level(r), r.tbt_deadline_ms is None, r.rid))
        if priority_level(victim) < priority_level(grower):
            return grower
        return victim

    # -- one engine tick -----------------------------------------------------

    def step(self):
        """One engine tick: admission, one prefill round, one decode
        round, then a metrics sample (queue depth, active seats, page
        occupancy overall and per priority class).  Each phase is a
        ``serve.<phase>`` span on the profiler's clock, and is timed
        into the telemetry's tick profiler when one is attached."""
        self.metrics.begin(self.clock())
        self._tick += 1
        with self._phase("admission"):
            self._admit_from_queue()
        with self._phase("prefill"):
            self.policy.prefill_tick()
        with self._phase("decode"):
            self.policy.decode_tick()
        with self._phase("bookkeeping"):
            self._tick_bookkeeping()
        tel = self.telemetry
        if tel is not None and tel.profiler is not None:
            tel.profiler.note_tick()

    def _phase(self, name: str):
        """Step phase ``name`` as a span ``serve.<name>`` on the
        profiler's clock (``jax.profiler.TraceAnnotation``, always on:
        with no trace active it costs well under a microsecond), timed
        into the telemetry's tick profiler when one is attached."""
        tel = self.telemetry
        if tel is None or tel.profiler is None:
            return jax.profiler.TraceAnnotation("serve." + name)
        return _TimedPhase(tel.profiler, name)

    def _tick_bookkeeping(self):
        """The tick's closing metrics sample."""
        cached, evictions = self.policy.cache_stats()
        pages_by_class: Dict[str, int] = {}
        for r in self.seats.values():
            if r.pages:
                pages_by_class[r.priority] = (
                    pages_by_class.get(r.priority, 0) + len(r.pages))
        self.metrics.tick(queued=len(self.queue), active=len(self.seats),
                          pages_in_use=self.policy.pages_in_use(),
                          cached_pages=cached, evictions=evictions,
                          pages_by_class=pages_by_class,
                          now=self.clock())

    def run(self, max_ticks: Optional[int] = None) -> List[Request]:
        """Tick until every submitted request finishes.

        Args:
          max_ticks: stall bound; the engine's ``default_max_ticks``
              when None.

        Returns:
          All finished :class:`Request` objects, completion order.

        Raises:
          SchedulerStallError: ticks ran out with work still pending;
              the message names each stalled request as
              ``rid(priority)``."""
        if max_ticks is None:
            max_ticks = self.default_max_ticks
        t = 0
        while (self.queue or self.seats) and t < max_ticks:
            self.step()
            t += 1
        if self.queue or self.seats:
            stalled = sorted(list(self.queue) + list(self.seats.values()),
                             key=lambda r: r.rid)
            msg = (f"run() exhausted max_ticks={max_ticks} with "
                   f"{len(self.queue)} queued and {len(self.seats)} "
                   f"active requests: "
                   + ", ".join(f"{r.rid}({r.priority})" for r in stalled))
            if self.telemetry is not None:
                # dump the flight recorder + full engine state before
                # raising: the stall is exactly when the evidence is hot
                self.telemetry.write_postmortem(
                    "SchedulerStallError: " + msg,
                    engines={self.engine_id or "engine": self})
            raise SchedulerStallError(msg)
        return self.finished


# ---------------------------------------------------------------------------
# KV placement policies
# ---------------------------------------------------------------------------

class FixedSlotPolicy:
    """Dense fixed-slot placement: B cache slots of ``max_len`` tokens,
    whole-prompt prefill scattered into the slot.  Wastes
    ``max_len - len`` KV tokens per short request, but its per-request
    state is constant-size, so it covers SSM / encoder-decoder / frontend
    archs and is the arithmetic oracle for the paged path.

    The cache carries one extra *scratch position* at index ``max_len``
    (the fixed-slot analogue of the paged path's scratch page 0): idle
    slots still ride through the batched ``decode_step``, and routing
    their token-0 writes to the scratch position keeps them from
    rewriting KV at whatever position the slot's previous occupant left
    behind.  No live query ever attends to it (live positions are
    < ``max_len`` and the causal mask drops keys beyond the query)."""

    def __init__(self, cfg, params, *, slots: int, max_len: int,
                 rules: LogicalRules, opts: Optional[M.RunOptions]):
        if cfg.mla or cfg.first_k_dense:
            # the slot placement below knows one level of K/V caches
            raise ValueError(f"{cfg.name}: latent caches and leading dense "
                             "layers are served by PagedServingEngine")
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.rules = rules
        self.opts = opts or M.RunOptions(q_chunk=min(max_len, 512))
        self.cache = M.init_cache(cfg, slots, max_len + 1, self.opts)
        # next write position; max_len = scratch (slot idle)
        self.pos = jnp.full((slots,), max_len, jnp.int32)
        self._decode = jax.jit(
            lambda p, c, t, q: M.decode_step(p, cfg, c, t, q, rules, self.opts))
        self._prefill = jax.jit(
            lambda p, b: M.prefill(p, cfg, b, rules, self.opts))

    def bind(self, sched: Scheduler) -> None:
        """Attach the owning :class:`Scheduler` (called once, by its
        constructor)."""
        self.sched = sched

    def pages_in_use(self) -> int:
        """Always 0 — fixed slots are not page-accounted."""
        return 0

    def cache_stats(self) -> Tuple[int, int]:
        """(cached reclaimable pages, evictions) — both always 0 here;
        the fixed-slot layout has no prefix cache."""
        return 0, 0

    def validate(self, req: Request) -> None:
        """Reject a request this layout could never place.

        Raises:
          ValueError: empty prompt, prompt >= ``max_len``, or
              ``prompt + max_new_tokens`` > ``max_len`` (decode would
              clamp into the last cache slot and corrupt KV)."""
        total = len(req.prompt) + req.max_new_tokens
        if len(req.prompt) == 0:
            raise ValueError("empty prompt")
        if len(req.prompt) >= self.max_len:
            raise ValueError(f"prompt length {len(req.prompt)} >= "
                             f"max_len={self.max_len}")
        if total > self.max_len:
            raise ValueError(f"request needs {total} tokens > "
                             f"max_len={self.max_len}; decode would clamp "
                             "into the last cache slot and corrupt KV")

    def try_admit(self, req: Request, seat: int) -> bool:
        """Always True: the seat itself is the only fixed-slot
        resource (every slot is pre-provisioned for ``max_len``)."""
        return True

    def release(self, req: Request) -> None:
        """Return a finished request's slot: the write position parks
        on the scratch index so the idle slot's pass through the
        batched decode stops touching the KV its previous occupant
        wrote."""
        self.pos = self.pos.at[req.slot].set(self.max_len)

    def preempt(self, req: Request) -> None:
        """Hook-surface parity with PagedPolicy (the fixed-slot engine
        never preempts on its own — the seat is the only resource — but
        ``Scheduler.preempt`` works against either policy): the slot goes
        back to scratch and the request replays prompt + generated[:-1]
        on re-admission."""
        self.pos = self.pos.at[req.slot].set(self.max_len)
        req.resume_tokens = np.concatenate(
            [req.prompt, np.asarray(req.generated[:-1], np.int32)])
        req.prefill_pos = 0

    def prefill_tick(self) -> None:
        """Whole-prompt prefill for every seat admitted this tick, in rid
        order (so the newly admitted request decodes in the same tick —
        the pre-refactor fixed-slot cadence).  First tokens for the whole
        admission burst are sampled in ONE batched call after all the
        prefills dispatch, not one blocking sync per request."""
        pending = sorted((r for r in self.sched.seats.values()
                          if r.prefill_pos < len(r.prefill_src)),
                         key=lambda r: r.rid)
        ready = []
        for req in pending:
            row = self._prefill_one(req)
            if row is not None:
                ready.append((req, row))
        self.sched._emit_first_tokens(ready)

    def _prefill_one(self, req: Request):
        slot = req.slot
        src = req.prefill_src
        P = len(src)
        batch = {"tokens": jnp.asarray(src, jnp.int32)[None]}
        if self.cfg.frontend == "vision":
            batch["patches"] = jnp.zeros(
                (1, self.cfg.frontend_len, self.cfg.frontend_dim), jnp.float32)
        if self.cfg.frontend == "audio":
            batch["audio"] = jnp.zeros(
                (1, self.cfg.encoder_len, self.cfg.frontend_dim), jnp.float32)
        logits, row_cache = self._prefill(self.params, batch)

        # scatter the single-row cache into this slot's region (the +1
        # pads through the scratch position at index max_len)
        def place(full, row, k2):
            if k2 in ("k", "v"):                 # (G,1,P,KVH,hd) -> slot, pad seq
                pad = self.max_len + 1 - row.shape[2]
                row = jnp.pad(row, [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)])
                return full.at[:, slot].set(row[:, 0])
            if k2 in ("ck", "cv", "conv", "ssm"):
                return full.at[:, slot].set(row[:, 0])
            return full
        self.cache = {
            pos: {k2: place(self.cache[pos][k2], row_cache[pos][k2], k2)
                  for k2 in self.cache[pos]}
            for pos in self.cache}
        self.pos = self.pos.at[slot].set(P)
        req.prefill_pos = P
        self.sched.metrics.prefill_tokens += P
        if req.resume_tokens is None:
            return logits[0, -1]         # first token sampled in the batch
        # replay after a preemption — the TTFT token was already emitted;
        # decode resumes by feeding generated[-1]
        return None

    def decode_tick(self) -> None:
        """One token for every active slot (prefill completes in the
        admission tick, so every seat is decode-ready)."""
        sched = self.sched
        if not sched.seats:
            return
        tok = np.zeros((self.slots, 1), np.int32)
        adv = np.zeros((self.slots,), np.int32)
        for slot, req in sched.seats.items():
            tok[slot, 0] = req.generated[-1]
            adv[slot] = 1
        # this tick's two uploads: the token batch and the advance mask
        logits, self.cache = self._decode(
            self.params, self.cache,
            jnp.asarray(tok),  # repro-lint: disable=RL001
            self.pos)
        toks = sched._sample_decode_batch(logits[:, -1], list(sched.seats))
        active = list(sched.seats.items())
        # advance positions BEFORE emitting, in ONE vectorized add (a
        # per-slot .at[slot].add(1) loop dispatched K ops per tick): a
        # token that finishes its request triggers release(), whose
        # scratch-position reset must not be clobbered by this tick's
        # increment
        self.pos = self.pos + jnp.asarray(adv)  # repro-lint: disable=RL001
        for slot, req in active:
            sched._emit_decode_token(req, toks[slot])


class PagedPolicy:
    """Paged-KV placement (see module docstring): shared page pool,
    chunked prefill, page-table decode, refcounted prefix caching with
    copy-on-write of the last partially shared page, and — with
    ``lazy_pages`` (default) — on-demand page growth with
    preempt-and-recompute under pressure."""

    def __init__(self, cfg, params, *, page_size: int, num_pages: int,
                 max_seats: int, max_seq_len: int, prefill_chunk: int,
                 rules: LogicalRules, opts: Optional[M.RunOptions],
                 prefix_cache: bool = True, lazy_pages: bool = True,
                 watermark: float = 0.05, fused: bool = True,
                 kv_dtype: Optional[str] = None,
                 class_precision: Optional[Dict[str, str]] = None):
        if not M.paged_cache_supported(cfg):
            raise ValueError(
                f"{cfg.name}: paged KV needs a pure-attention decoder; "
                "use ServingEngine for ssm/enc-dec/frontend archs")
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.max_seats = max_seats
        self.max_seq_len = max_seq_len
        self.prefill_chunk = prefill_chunk
        self.rules = rules
        self.opts = opts or M.RunOptions(q_chunk=min(max_seq_len, 512))
        # KV pool storage precision (uniform per engine; None = the
        # config's compute dtype, the pre-quantization layout) and the
        # per-SLO-class precision floors enforced by validate()
        if kv_dtype is not None:
            mixed_precision.kv_storage_dtype(kv_dtype)   # validate early
        self.kv_dtype = kv_dtype
        self.kv_dtype_name = kv_dtype or (
            "bf16" if jnp.dtype(cfg.compute_dtype) == jnp.bfloat16 else "f32")
        self.class_precision = dict(class_precision or {})
        for cls, want in self.class_precision.items():
            if cls not in PRIORITIES:
                raise ValueError(
                    f"class_precision names unknown class {cls!r}; "
                    f"expected one of {sorted(PRIORITIES)}")
            mixed_precision.kv_precision_bits(want)       # validate dtype
        self.page_bytes = M.paged_page_bytes(cfg, page_size, kv_dtype)

        self.bm = BlockManager(num_pages, page_size, prefix_cache=prefix_cache,
                               page_bytes=self.page_bytes)
        self.n_tables = max(1, -(-max_seq_len // page_size))
        self.lazy = lazy_pages
        # admission headroom so live requests usually grow unopposed
        # (>=1 page whenever a watermark is requested; 0 disables the
        # gate); waived when the pool is idle — a lone max-length prompt
        # must still be startable
        self.watermark_pages = (
            max(1, math.ceil(watermark * self.bm.capacity))
            if lazy_pages and watermark > 0 else 0)
        if lazy_pages and self.n_tables > self.bm.capacity:
            # liveness bound: any admitted request (total <= max_seq_len)
            # must be completable with the whole pool to itself, or the
            # preempt/recompute loop could never converge
            raise ValueError(
                f"lazy_pages needs the pool to cover one max-length "
                f"request: max_seq_len={max_seq_len} spans "
                f"{self.n_tables} pages > capacity {self.bm.capacity}; "
                "raise num_pages, lower max_seq_len, or set "
                "lazy_pages=False")
        self.page_table = np.zeros((max_seats, self.n_tables), np.int32)
        self.pos = np.zeros((max_seats,), np.int32)     # next write position
        self.fused = fused
        self._prefill_row: Optional[Tuple[int, jnp.ndarray]] = None
        # device mirrors of the serving state, rebuilt only on churn
        # (self._dirty); between churn events decode ticks run entirely
        # from the arrays the previous fused tick returned, so the only
        # per-tick host<->device traffic is the token vector coming back
        self._dev: Optional[Dict[str, jnp.ndarray]] = None
        self._dirty = True
        self._init_model_state(num_pages)

    def _init_model_state(self, num_pages: int) -> None:
        """Allocate the device KV pool and compile the jitted tick
        functions.  Split out of ``__init__`` so a model-free policy
        (:class:`~repro.runtime.workload.OraclePolicy`) can inherit all
        the placement bookkeeping above while replacing the device
        state with host stubs."""
        cfg, rules = self.cfg, self.rules
        self.cache = M.init_paged_cache(cfg, num_pages, self.page_size,
                                        kv_dtype=self.kv_dtype)

        self._step_fn = jax.jit(
            lambda p, c, t, q, pt, nv: M.paged_decode_step(
                p, cfg, c, t, q, pt, nv, rules, self.opts))
        # prefill variant of the step: start/valid-count travel as ONE
        # (2,) int32 upload split inside the trace, and the seat's page
        # table row arrives pre-uploaded (it is invariant across a
        # request's chunks — pages are placed at admission, growth only
        # happens in decode — so prefill_tick caches the device copy
        # per request instead of re-uploading it every chunk)
        self._prefill_fn = jax.jit(
            lambda p, c, t, meta, pt: M.paged_decode_step(
                p, cfg, c, t, meta[:1], pt, meta[1:], rules, self.opts))
        # donate the pool so copy-on-write is an in-place one-page update,
        # not a fresh copy of the whole KV pool (donation is a no-op on
        # CPU and would only warn there)
        donate = (0,) if jax.default_backend() != "cpu" else ()
        self._cow_fn = jax.jit(M.copy_paged_page, donate_argnums=donate)

        # fused one-dispatch tick: model step + batched sampling in one
        # jitted call over device-resident state.  Every argument keeps a
        # fixed (max_seats,)-based shape so admission/finish/preemption
        # churn never retraces; cache / last-token / pos / table / step
        # are donated (functional in-place update) off-CPU.  Arg order:
        # 0=params 1=cache 2=last 3=pos 4=table 5=nv 6=temp 7=top_k
        # 8=top_p 9=seed 10=rid 11=step; outputs alias 1->cache, 2->toks,
        # 3->pos, 4->table, 11->step.
        fdonate = ((1, 2, 3, 4, 11)
                   if jax.default_backend() != "cpu" else ())
        self._fused_fn = jax.jit(
            lambda p, c, last, q, pt, nv, t, tk, tp, sd, rd, st:
                M.fused_decode_tick(p, cfg, c, last, q, pt, nv, t, tk, tp,
                                    sd, rd, st, rules, self.opts),
            donate_argnums=fdonate)

    def bind(self, sched: Scheduler) -> None:
        """Attach the owning :class:`Scheduler` (called once, by its
        constructor)."""
        self.sched = sched

    def pages_in_use(self) -> int:
        """Pages currently referenced by at least one live request."""
        return self.bm.in_use

    def cache_stats(self) -> Tuple[int, int]:
        """(reclaimable prefix-cache pages, evictions so far) from the
        underlying :class:`BlockManager`."""
        return self.bm.cached, self.bm.evictions

    def validate(self, req: Request) -> None:
        """Reject a request this pool could never place.

        Raises:
          ValueError: empty prompt; ``prompt + max_new_tokens`` >
              ``max_seq_len``; the request's SLO class carries a
              precision floor (``class_precision``) this pool's
              ``kv_dtype`` does not meet; or (reserved mode only) a
              page demand over the whole pool's capacity.  In lazy
              mode the constructor's ``n_tables <= capacity`` bound
              already makes ``max_seq_len`` the per-request
              feasibility limit."""
        total = len(req.prompt) + req.max_new_tokens
        if len(req.prompt) == 0:
            raise ValueError("empty prompt")
        want = self.class_precision.get(req.priority)
        if want is not None and (mixed_precision.kv_precision_bits(
                self.kv_dtype_name)
                < mixed_precision.kv_precision_bits(want)):
            raise ValueError(
                f"request class {req.priority!r} requires kv precision "
                f">= {want} but this engine's pool stores "
                f"{self.kv_dtype_name}; route it to a full-precision "
                "replica (see runtime.router) or drop the class's "
                "precision floor")
        if total > self.max_seq_len:
            raise ValueError(f"request needs {total} tokens > "
                             f"max_seq_len={self.max_seq_len}")
        if not self.lazy and self.bm.pages_needed(total) > self.bm.capacity:
            # up-front reservation must fit the pool; in lazy mode the
            # constructor's n_tables <= capacity bound already makes
            # max_seq_len the per-request feasibility limit
            raise ValueError(f"request needs {self.bm.pages_needed(total)} "
                             f"pages > pool capacity {self.bm.capacity}")

    # -- admission: seat + page budget + prefix reuse -------------------------

    def try_admit(self, req: Request, seat: int) -> bool:
        """Place ``req`` at ``seat`` if the pool allows: reserve its
        pages (prompt-only in lazy mode, prompt + full budget in
        reserved mode), take refs on cached prefix pages, copy-on-write
        a partially matching page, and point the seat's page-table row
        at the result.  Returns False — with no side effects — when
        the pool cannot cover the demand (the scheduler keeps the
        request queued)."""
        # a starved queue head re-attempts every tick; skip the O(prompt)
        # prefix match until the pool/index actually changed
        if req.match_version == self.bm.version:
            return False
        src = req.prefill_src
        if self.lazy:
            # reserve only the prompt's pages; decode grows on demand.
            # keep watermark headroom unless the pool is idle
            need = self.bm.pages_needed(len(src))
            gate = self.watermark_pages if self.sched.seats else 0
        else:
            need = self.bm.pages_needed(len(src) + req.max_new_tokens)
            gate = 0
        match = self.bm.match_prefix(src)
        # feasibility before any side effect: acquiring a reclaimable
        # matched (or CoW-source) page consumes one allocatable slot, so
        # a starved head request must not churn refcounts/LRU order
        # every tick
        pinned = list(match.pages)
        if match.cow_src is not None:
            pinned.append(match.cow_src)
        reclaimed = sum(1 for pg in pinned if self.bm.refcount(pg) == 0)
        if not self.bm.can_alloc(need - len(match.pages) + reclaimed + gate):
            if match.cow_src is not None:
                # the CoW transient (source + copy live at once) can be
                # what doesn't fit; forgo the partial-page match rather
                # than defer — the partial page is recomputed from
                # tokens, full-page shares are kept
                match = PrefixMatch(match.pages, None,
                                    len(match.pages) * self.page_size)
                pinned = list(match.pages)
                reclaimed = sum(1 for pg in pinned
                                if self.bm.refcount(pg) == 0)
            if not self.bm.can_alloc(need - len(match.pages)
                                     + reclaimed + gate):
                req.match_version = self.bm.version
                return False
        for pg in pinned:                        # pin shares AND the CoW
            self.bm.acquire(pg, req.rid)         # source before alloc can
        fresh = self.bm.alloc(need - len(match.pages), req.rid)  # evict them
        if fresh is None:                        # unreachable after the guard
            self.bm.free(pinned)
            return False
        if match.cow_src is not None:
            # the partially matched page: copy, then own the copy — its
            # tail will be overwritten with this request's own tokens.
            # The pin above keeps the source out of alloc's reach (it
            # could otherwise be evicted and handed back as fresh[0],
            # self-copying a donated buffer); drop it once copied
            self.cache = self._cow_fn(self.cache, match.cow_src, fresh[0])
            self.bm.free([match.cow_src])
        req.pages = match.pages + fresh
        req.prefill_pos = req.cached_tokens = match.n_cached
        req.registered_pages = len(match.pages)
        row = np.zeros((self.n_tables,), np.int32)
        row[:len(req.pages)] = req.pages
        self.page_table[seat] = row
        self.pos[seat] = 0
        self._dirty = True
        return True

    def release(self, req: Request) -> None:
        """Drop a finished request's page refs and clear its page-table
        row; registered prompt pages park reclaimable in the prefix
        index, everything else returns to the free list."""
        self.bm.free(req.pages)
        self.page_table[req.slot] = 0
        self.pos[req.slot] = 0
        self._dirty = True
        self._prefill_row = None

    def preempt(self, req: Request) -> None:
        """Free the request's placement for replay: refcounts drop
        (shared prefix pages stay live for their other holders;
        registered full prompt pages park reclaimable, so the
        re-admission prefix match revives them), and the request will
        re-prefill ``prompt + generated[:-1]`` before feeding
        ``generated[-1]`` back through the normal decode path."""
        self.bm.free(req.pages)
        self.page_table[req.slot] = 0
        self.pos[req.slot] = 0
        self._dirty = True
        self._prefill_row = None
        req.resume_tokens = np.concatenate(
            [req.prompt, np.asarray(req.generated[:-1], np.int32)])
        req.pages = []
        req.prefill_pos = 0
        req.cached_tokens = 0
        req.registered_pages = 0
        req.match_version = None

    # -- prefill / decode ------------------------------------------------------

    def prefill_tick(self) -> None:
        """One prompt chunk for the oldest mid-prefill request (chunked
        prefill: long prompts share the engine with everyone's decode).
        Requests with a prefix-cache hit start at ``cached_tokens``;
        preempted requests replay ``prompt + generated[:-1]``."""
        cands = [r for r in self.sched.seats.values()
                 if r.prefill_pos < len(r.prefill_src)]
        if not cands:
            return
        req = min(cands, key=lambda r: r.rid)
        seat = req.slot
        src = req.prefill_src
        start = req.prefill_pos
        chunk = src[start:start + self.prefill_chunk]
        c = len(chunk)
        tok = np.zeros((1, self.prefill_chunk), np.int32)
        tok[0, :c] = chunk
        meta = np.asarray([start, c], np.int32)
        if self._prefill_row is None or self._prefill_row[0] != req.rid:
            # upload the seat's table row once per request, not per
            # chunk (invalidated on release/preempt; the row cannot
            # change mid-prefill — see _prefill_fn)
            self._prefill_row = (
                req.rid,
                jnp.asarray(  # repro-lint: disable=RL001
                    self.page_table[seat:seat + 1]))
        # per-chunk payload: the token chunk and the (start, count) pair
        logits, self.cache = self._prefill_fn(
            self.params, self.cache,
            jnp.asarray(tok),   # repro-lint: disable=RL001
            jnp.asarray(meta),  # repro-lint: disable=RL001
            self._prefill_row[1])
        req.prefill_pos += c
        self.sched.metrics.prefill_tokens += c
        self.sched._trace("prefill_chunk", req.rid)
        self._register_full_pages(req)
        if req.prefill_pos == len(src):
            self.pos[seat] = len(src)
            self._dirty = True           # seat joins the decoding set
            if req.resume_tokens is None:
                self.sched._emit_first_tokens([(req, logits[0, c - 1])])
            # else: replay — TTFT token already emitted before the
            # preemption; decode resumes by feeding generated[-1]

    def _register_full_pages(self, req: Request) -> None:
        """Publish every page now fully covered by prefill tokens to the
        prefix index (idempotent for pages the request shares)."""
        if not self.bm.prefix_cache:
            return
        src = req.prefill_src
        full = req.prefill_pos // self.page_size
        while req.registered_pages < full:
            i = req.registered_pages
            self.bm.register_prefix(src[:(i + 1) * self.page_size],
                                    req.pages[i])
            req.registered_pages += 1

    def _decoding_seats(self) -> List[int]:
        return [s for s, r in self.sched.seats.items()
                if r.prefill_pos >= len(r.prefill_src)]

    def _grow_tick(self) -> None:
        """Lazy mode: hand each decoding seat the page its next write
        needs (one page per boundary crossing), oldest request first.
        When the pool cannot grow, preempt the
        :meth:`Scheduler.pick_victim` choice — lowest priority class
        first, youngest within a class, never a strictly higher class
        than the grower's (then the grower evicts itself) — until the
        allocation succeeds or the grower is gone."""
        sched = self.sched
        for s in sorted(self._decoding_seats(),
                        key=lambda s: sched.seats[s].rid):
            req = sched.seats.get(s)
            if req is None:                  # preempted for an older seat
                continue
            if self.pos[s] < len(req.pages) * self.page_size:
                continue                     # next write is covered
            pg = self.bm.try_grow(req.rid)
            while pg is None:
                victims = [sched.seats[v] for v in self._decoding_seats()]
                victim = sched.pick_victim(victims, req)
                sched.preempt(victim)
                if victim is req:
                    break                    # grower evicted itself
                pg = self.bm.try_grow(req.rid)
            if pg is not None:
                self.page_table[s, len(req.pages)] = pg
                req.pages.append(pg)
                self._dirty = True       # table row changed on host

    def _sync_device(self) -> None:
        """Rebuild the device-resident tick state from the host mirrors
        after a churn event (admit / finish / preempt / page growth /
        prefill completion).  Steady-state decode ticks never call this —
        they run entirely off the arrays the previous fused tick
        returned, and the host mirrors (``self.pos``/``self.page_table``,
        which bookkeeping and tests introspect) stay authoritative for
        scheduling decisions.  Every array keeps a fixed
        ``(max_seats,)``-based shape and dtype so the fused jit never
        retraces."""
        A = self.max_seats
        last = np.zeros((A,), np.int32)
        nv = np.zeros((A,), np.int32)
        temp = np.zeros((A,), np.float32)
        top_k = np.zeros((A,), np.int32)
        top_p = np.ones((A,), np.float32)
        seed = np.zeros((A,), np.uint32)
        rid = np.zeros((A,), np.uint32)
        step = np.zeros((A,), np.uint32)
        for s, r in self.sched.seats.items():
            if r.prefill_pos < len(r.prefill_src):
                continue                 # still prefilling: stays masked
            nv[s] = 1
            last[s] = r.generated[-1]
            sp = r.sampling
            temp[s] = sp.temperature
            top_k[s] = sp.top_k
            top_p[s] = sp.top_p
            seed[s] = sp.seed & 0xFFFFFFFF
            rid[s] = r.rid & 0xFFFFFFFF
            step[s] = len(r.generated)
        self._dev = {
            "last": jnp.asarray(last), "pos": jnp.asarray(self.pos),
            "table": jnp.asarray(self.page_table), "nv": jnp.asarray(nv),
            "temp": jnp.asarray(temp), "top_k": jnp.asarray(top_k),
            "top_p": jnp.asarray(top_p), "seed": jnp.asarray(seed),
            "rid": jnp.asarray(rid), "step": jnp.asarray(step),
        }
        self._dirty = False

    def decode_tick(self) -> None:
        """One token for every seat whose prefill is complete (growing
        page tables first in lazy mode).

        Fused mode (default): ONE jitted dispatch runs the model step
        and the batched sampler over device-resident state, and the only
        host<->device traffic for the tick is the ``(max_seats,)`` int32
        token vector coming back.  ``fused=False`` keeps the pre-fusion
        per-tick path (host-built token/nv arrays, host-side sampling) —
        the equivalence oracle the fused path is pinned token-identical
        to."""
        sched = self.sched
        if self.lazy:
            with sched._phase("decode/grow"):
                self._grow_tick()
        decoding = self._decoding_seats()
        if not decoding:
            return
        if self._dirty:
            # table/nv re-upload only on churn (admit completion,
            # finish, preempt, growth all set self._dirty); between
            # churn events the device mirrors are reused
            with sched._phase("decode/sync"):
                self._sync_device()
        d = self._dev
        if not self.fused:
            tok = np.zeros((self.max_seats, 1), np.int32)
            for s in decoding:
                tok[s, 0] = sched.seats[s].generated[-1]
            # per-tick payload: the token batch and the advancing
            # positions
            with sched._phase("decode/dispatch"):
                logits, self.cache = self._step_fn(
                    self.params, self.cache,
                    jnp.asarray(tok),       # repro-lint: disable=RL001
                    jnp.asarray(self.pos),  # repro-lint: disable=RL001
                    d["table"], d["nv"])
            with sched._phase("decode/host"):
                toks = sched._sample_decode_batch(logits[:, 0], decoding)
        else:
            # ``fused_decode_tick`` is the dispatch span's older name,
            # which bench/tracereduce.py still keeps and attributes
            # idle gaps to
            with sched._phase("decode/dispatch"), \
                    jax.profiler.TraceAnnotation("fused_decode_tick"):
                toks_dev, self.cache, d["pos"], d["step"], d["table"] = \
                    self._fused_fn(self.params, self.cache, d["last"],
                                   d["pos"], d["table"], d["nv"],
                                   d["temp"], d["top_k"], d["top_p"],
                                   d["seed"], d["rid"], d["step"])
            d["last"] = toks_dev         # this tick's token = next input
            with sched._phase("decode/host"):
                # the tick's ONE device->host sync
                toks = np.asarray(toks_dev)  # repro-lint: disable=RL001
        with sched._phase("decode/sample"):
            for s in decoding:
                req = sched.seats[s]
                self.pos[s] += 1
                sched._emit_decode_token(req, int(toks[s]))


# ---------------------------------------------------------------------------
# Engine façades (public API)
# ---------------------------------------------------------------------------

class ServingEngine(Scheduler):
    """Fixed-slot continuous-batching engine: the Scheduler bound to
    :class:`FixedSlotPolicy`.  Serves every arch (SSM, enc-dec, frontend)
    and is the equivalence oracle for the paged engine.

    ``admission`` selects the queue policy (``"fcfs"`` default /
    ``"slo"``) and ``aging_ticks`` its anti-starvation bound — see
    :class:`SLOAdmission` and docs/serving.md."""

    default_max_ticks = 10_000

    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 256,
                 rules: LogicalRules = SINGLE_DEVICE_RULES,
                 opts: Optional[M.RunOptions] = None,
                 sampler: Optional[Sampler] = None,
                 admission="fcfs", aging_ticks: int = 64,
                 clock=None, record_trace: bool = True, telemetry=None):
        policy = FixedSlotPolicy(cfg, params, slots=slots, max_len=max_len,
                                 rules=rules, opts=opts)
        super().__init__(policy, max_seats=slots, sampler=sampler,
                         admission=admission, aging_ticks=aging_ticks,
                         clock=clock, record_trace=record_trace,
                         telemetry=telemetry)
        self.cfg = cfg
        self.params = params
        self.B = slots
        self.max_len = max_len
        self.rules = rules
        self.opts = policy.opts

    @property
    def active(self) -> Dict[int, Request]:
        return self.seats

    @property
    def cache(self):
        return self.policy.cache

    @property
    def pos(self):
        return self.policy.pos


class PagedServingEngine(Scheduler):
    """Paged-KV continuous-batching engine: the Scheduler bound to
    :class:`PagedPolicy` (shared page pool, chunked prefill, refcounted
    prefix caching — ``prefix_cache=False`` disables sharing for A/B
    comparisons).  ``lazy_pages`` (default True) reserves only prompt
    pages at admission and grows on demand, preempting the youngest
    decoding request (recompute-on-readmission) under page pressure;
    ``lazy_pages=False`` restores up-front full reservation.
    ``watermark`` is the lazy admission gate's free-page headroom as a
    fraction of pool capacity (≥1 page; waived on an idle pool).
    ``fused`` (default True) runs each decode tick as ONE jitted
    dispatch — model step plus batched on-device sampling over
    device-resident pos/page-table/last-token state — so a single
    ``(max_seats,)`` token vector is the tick's only host↔device
    round-trip; ``fused=False`` keeps the pre-fusion per-tick path
    (the equivalence oracle).
    ``admission`` selects the queue policy (``"fcfs"`` default /
    ``"slo"``) and ``aging_ticks`` its anti-starvation bound — see
    :class:`SLOAdmission` and docs/serving.md.
    ``kv_dtype`` picks the KV pool's storage precision
    (``f32``/``bf16``/``fp8``/``int8``; None = the config's compute
    dtype — the pre-quantization layout, token streams bit-identical
    to it).  Quantized pools store per-(token, head) scales next to
    the pages and dequantize inside the decode path, so the same
    byte budget holds ~4× the tokens at hd=64 (docs/serving.md
    §"Quantized KV pages").  ``class_precision`` maps SLO classes to
    minimum precisions (e.g. ``{"premium": "f32"}``): a request whose
    class's floor this pool cannot meet is rejected at submit — the
    fleet router uses the same map to route such classes to
    full-precision replicas."""

    default_max_ticks = 100_000

    def __init__(self, cfg, params, *, page_size: int = 16,
                 num_pages: int = 64, max_seats: int = 8,
                 max_seq_len: int = 256, prefill_chunk: int = 32,
                 rules: LogicalRules = SINGLE_DEVICE_RULES,
                 opts: Optional[M.RunOptions] = None,
                 sampler: Optional[Sampler] = None,
                 prefix_cache: bool = True, lazy_pages: bool = True,
                 watermark: float = 0.05, fused: bool = True,
                 admission="fcfs", aging_ticks: int = 64,
                 kv_dtype: Optional[str] = None,
                 class_precision: Optional[Dict[str, str]] = None,
                 clock=None, record_trace: bool = True,
                 telemetry=None, policy_cls: Optional[type] = None):
        # policy_cls swaps the placement+arithmetic implementation while
        # keeping every Scheduler behavior: the load harness passes
        # workload.OraclePolicy (model-free hash logits) here
        policy = (policy_cls or PagedPolicy)(
            cfg, params, page_size=page_size,
            num_pages=num_pages, max_seats=max_seats,
            max_seq_len=max_seq_len,
            prefill_chunk=prefill_chunk, rules=rules,
            opts=opts, prefix_cache=prefix_cache,
            lazy_pages=lazy_pages, watermark=watermark,
            fused=fused, kv_dtype=kv_dtype,
            class_precision=class_precision)
        super().__init__(policy, max_seats=max_seats, sampler=sampler,
                         page_capacity=policy.bm.capacity,
                         admission=admission, aging_ticks=aging_ticks,
                         clock=clock, record_trace=record_trace,
                         telemetry=telemetry)
        self.metrics.kv_dtype = policy.kv_dtype_name
        self.metrics.page_bytes = policy.page_bytes
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.max_seq_len = max_seq_len
        self.prefill_chunk = prefill_chunk
        self.rules = rules
        self.opts = policy.opts

    @property
    def kv_dtype(self) -> str:
        """The pool's storage precision name (resolved)."""
        return self.policy.kv_dtype_name

    @property
    def bm(self) -> BlockManager:
        return self.policy.bm

    @property
    def n_tables(self) -> int:
        return self.policy.n_tables

    @property
    def cache(self):
        return self.policy.cache

    @property
    def page_table(self) -> np.ndarray:
        return self.policy.page_table

    @property
    def pos(self) -> np.ndarray:
        return self.policy.pos
