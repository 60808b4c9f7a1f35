"""Serving drivers: static-batch loop and the paged continuous engine.

``serve`` is the static path: one batch of equal-length prompts prefilled
in one shot, then lockstep decode (the decode_32k / long_500k dry-run
cells lower the same ``decode_step``).  ``serve_paged`` drives the
paged-KV continuous-batching engine (``runtime.serving.PagedServing
Engine`` — unified scheduler + refcounted prefix caching) over a
mixed-length request stream and reports engine metrics (TTFT, tokens/s,
page utilization, prefix-hit rate).

Both paths sample through ``runtime.sampler``: ``--temperature 0`` (the
default) is exact greedy argmax; ``--temperature/--top-k/--top-p/--seed``
select stochastic sampling, deterministic per (seed, request, step).
``--eos-id`` stops engine requests early (static batch decodes lockstep
and ignores it).

``serve_fleet`` (``--fleet``) drives a ``runtime.router.ModelFleet``:
several models — ``--models name[:replicas],...`` — served from one
process under one shared ``--total-pages`` host budget, with fleet-wide
metrics per model (see docs/serving.md §"Multi-model fleet").

``--tuning-preset alloc|full`` applies the host allocator / XLA
environment preset (tcmalloc ``LD_PRELOAD``, step-marker and
host-device-count ``XLA_FLAGS``) by re-exec'ing the interpreter once —
see :func:`build_tuning_env`.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
      --batch 4 --prompt-len 32 --gen 16
  PYTHONPATH=src python -m repro.launch.serve --engine paged \
      --arch qwen3-1.7b --requests 8 --gen 16 --temperature 0.8 --top-p 0.95
  PYTHONPATH=src python -m repro.launch.serve --fleet \
      --models qwen3-1.7b:2,llama3-8b --total-pages 64 --requests 12
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import (get_config, make_example_batch, reduced_config,
                           resolve_arch)
from repro.core.mixed_precision import KV_DTYPES
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.parallel.sharding import SINGLE_DEVICE_RULES
from repro.runtime.router import FleetModel, ModelFleet, parse_models_spec
from repro.runtime.sampler import Sampler, SamplingParams
from repro.runtime.serving import PagedServingEngine
from repro.runtime.telemetry import (MetricsServer, Telemetry,
                                     prometheus_text, write_perfetto)


# ---------------------------------------------------------------------------
# Allocator / XLA tuning presets
# ---------------------------------------------------------------------------
#
# The serving hot loop allocates host memory every tick (token vectors,
# metrics); the default glibc malloc serializes those on a global lock and
# XLA's default step-marker placement re-marks every dispatch.  The presets
# below bake the standard JAX-serving environment (tcmalloc preload, large-
# alloc report silencing, step marker on the outer loop, explicit host
# device count) into the launcher: LD_PRELOAD and XLA_FLAGS are read at
# process / backend init, so applying a preset re-execs the interpreter
# once with the adjusted environment.

TCMALLOC_PATH = "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4"
_TUNED_MARKER = "_REPRO_TUNED"          # guards against re-exec loops
_XLA_PRESET_FLAGS = ("--xla_step_marker_location=STEP_MARK_AT_TOP_LEVEL_WHILE_LOOP",
                     "--xla_force_host_platform_device_count=1")


def build_tuning_env(preset: str, env: Dict[str, str], *,
                     tcmalloc_path: str = TCMALLOC_PATH) -> Dict[str, str]:
    """Environment additions for a ``--tuning-preset`` (pure — no exec).

    ``off`` returns {}.  ``alloc`` preloads tcmalloc (skipped with no
    effect when the library is absent) and silences its large-allocation
    reports.  ``full`` adds the XLA flags on top: step marker on the
    outer while loop and a pinned host platform device count.  Existing
    ``LD_PRELOAD`` entries and ``XLA_FLAGS`` are appended to, never
    clobbered, and already-present values are left alone (idempotent)."""
    if preset == "off":
        return {}
    if preset not in ("alloc", "full"):
        raise ValueError(f"unknown tuning preset {preset!r}; "
                         "expected off/alloc/full")
    add: Dict[str, str] = {}
    if os.path.exists(tcmalloc_path):
        prior = env.get("LD_PRELOAD", "")
        if tcmalloc_path not in prior.split(":"):
            add["LD_PRELOAD"] = ":".join(
                p for p in (prior, tcmalloc_path) if p)
        if "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD" not in env:
            add["TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD"] = "60000000000"
    if preset == "full":
        flags = env.get("XLA_FLAGS", "")
        for flag in _XLA_PRESET_FLAGS:
            if flag.split("=")[0] not in flags:
                flags = " ".join(f for f in (flags, flag) if f)
        if flags != env.get("XLA_FLAGS", ""):
            add["XLA_FLAGS"] = flags
    return add


def apply_tuning_preset(preset: str) -> None:
    """Re-exec the interpreter with the preset environment applied.

    Must run before the first jax dispatch: ``LD_PRELOAD`` is consumed
    by the dynamic loader at process start and ``XLA_FLAGS`` at backend
    init, so neither can be changed in-process.  No-op (returns) when
    the preset is ``off``, the environment is already tuned (the
    ``_REPRO_TUNED`` marker — set on exec — breaks the exec loop), or
    the preset adds nothing."""
    if preset == "off" or os.environ.get(_TUNED_MARKER):
        return
    add = build_tuning_env(preset, dict(os.environ))
    env = {**os.environ, **add, _TUNED_MARKER: "1"}
    if not add:                          # nothing to change; just mark
        os.environ[_TUNED_MARKER] = "1"
        return
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def serve(arch: str, *, batch: int = 4, prompt_len: int = 32, gen: int = 16,
          reduced: bool = True, seed: int = 0,
          sampling: Optional[SamplingParams] = None):
    cfg = get_config(arch)
    if reduced:
        cfg = reduced_config(cfg)
    rules = SINGLE_DEVICE_RULES        # one device: no mesh is placed
    opts = M.RunOptions(q_chunk=min(prompt_len, 512), mesh=None)
    max_len = prompt_len + gen

    params = M.init_params(M.param_specs(cfg), jax.random.PRNGKey(seed),
                           dtype=jnp.float32)
    req = make_example_batch(cfg, "prefill", batch, prompt_len,
                             key=jax.random.PRNGKey(seed + 1))

    prefill_fn = jax.jit(lambda p, b: M.prefill(p, cfg, b, rules, opts))
    decode_fn = jax.jit(lambda p, c, t, q: M.decode_step(p, cfg, c, t, q,
                                                         rules, opts))
    sampler = Sampler()

    def pick(logits_last, step):
        """logits_last: (B, V) -> (B, 1) int32 via the shared sampler."""
        if sampling is None or sampling.greedy:
            return jnp.argmax(logits_last, axis=-1).astype(jnp.int32)[:, None]
        rows = np.asarray(logits_last)
        toks = [sampler.sample(rows[b], sampling, rid=b, step=step)
                for b in range(rows.shape[0])]
        return jnp.asarray(toks, jnp.int32)[:, None]

    t0 = time.perf_counter()
    logits, cache = prefill_fn(params, req)
    # grow cache to max_len along the KV seq dim
    def grow(pos_ent):
        out = {}
        for k, v in pos_ent.items():
            if k in ("k", "v"):
                pad = jnp.zeros(v.shape[:2] + (gen,) + v.shape[3:], v.dtype)
                out[k] = jnp.concatenate([v, pad], axis=2)
            else:
                out[k] = v
        return out
    cache = {pos: grow(ent) for pos, ent in cache.items()}
    t_prefill = time.perf_counter() - t0

    tok = pick(logits[:, -1], 0)
    out_tokens = [tok]
    t0 = time.perf_counter()
    for i in range(gen - 1):
        pos = jnp.full((batch,), prompt_len + i, jnp.int32)
        logits, cache = decode_fn(params, cache, tok, pos)
        tok = pick(logits[:, -1], i + 1)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.perf_counter() - t0

    gen_arr = jnp.concatenate(out_tokens, axis=1)
    return {
        "generated": gen_arr,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "tokens_per_s": batch * (gen - 1) / max(t_decode, 1e-9),
    }


def serve_paged(arch: str, *, requests: int = 8, gen: int = 16,
                page_size: int = 16, num_pages: int = 128,
                max_seats: int = 8, prefill_chunk: int = 32,
                reduced: bool = True, seed: int = 0,
                eos_id: Optional[int] = None,
                sampling: Optional[SamplingParams] = None,
                prefix_cache: bool = True,
                max_seq_len: Optional[int] = None,
                prompt_len: Optional[int] = None,
                lazy_pages: bool = True, watermark: float = 0.05,
                priority: str = "standard",
                deadline_ms: Optional[float] = None,
                tbt_deadline_ms: Optional[float] = None,
                admission: str = "fcfs", aging_ticks: int = 64,
                kv_dtype: Optional[str] = None,
                class_precision: Optional[Dict[str, str]] = None,
                telemetry: Optional[Telemetry] = None,
                metrics_port: Optional[int] = None):
    """Drive the paged engine over a request stream.

    ``max_seq_len`` bounds prompt + generation per request and defaults
    to ``(prompt_len or 3 * page_size) + gen``.  ``prompt_len`` fixes
    every prompt's length; when None, lengths are sampled to fit
    ``max_seq_len`` minus the generation budget.  Infeasible
    combinations raise here with the offending flags named instead of
    crashing inside ``submit``.

    ``kv_dtype`` picks the KV pool storage precision (``fp8``/``int8``
    quantize pages with per-token scales — see docs/serving.md
    §"Quantized KV pages"); ``class_precision`` maps SLO classes to
    minimum precisions, rejecting requests this pool cannot honor.

    ``admission`` picks the scheduler queue policy (``fcfs`` default,
    ``slo`` = priority + earliest-deadline-first with an ``aging_ticks``
    anti-starvation bound); ``priority`` (premium/standard/batch) and
    ``deadline_ms`` (TTFT deadline) are applied to every submitted
    request — one-class streams are plumbing demos; see
    benchmarks/serving_paged.py workload 4 for a mixed-class stream.

    ``telemetry`` attaches the observability plane (flight recorder /
    tick profiler — see docs/observability.md); ``metrics_port`` serves
    Prometheus text exposition of the live engine metrics on
    127.0.0.1 for the duration of the run (0 = ephemeral port)."""
    if max_seq_len is None:
        max_seq_len = (prompt_len if prompt_len else 3 * page_size) + gen
    if prompt_len is not None and prompt_len + gen > max_seq_len:
        raise ValueError(
            f"--prompt-len {prompt_len} + --gen {gen} exceeds "
            f"--max-seq-len {max_seq_len}")
    if prompt_len is None and max_seq_len - gen < 2:
        raise ValueError(
            f"--max-seq-len {max_seq_len} leaves no room for prompts "
            f"after --gen {gen}; raise it or pass --prompt-len")
    cfg = get_config(arch)
    if reduced:
        cfg = reduced_config(cfg)
    params = M.init_params(M.param_specs(cfg), jax.random.PRNGKey(seed),
                           dtype=jnp.float32)
    eng = PagedServingEngine(cfg, params, page_size=page_size,
                             num_pages=num_pages, max_seats=max_seats,
                             max_seq_len=max_seq_len,
                             prefill_chunk=prefill_chunk,
                             prefix_cache=prefix_cache,
                             lazy_pages=lazy_pages, watermark=watermark,
                             admission=admission, aging_ticks=aging_ticks,
                             kv_dtype=kv_dtype,
                             class_precision=class_precision,
                             telemetry=telemetry)
    rng = np.random.default_rng(seed)
    for _ in range(requests):
        plen = (prompt_len if prompt_len
                else int(rng.integers(1, max_seq_len - gen)))
        eng.submit(rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                   max_new_tokens=int(rng.integers(2, gen + 1)),
                   eos_id=eos_id, sampling=sampling,
                   priority=priority, deadline_ms=deadline_ms,
                   tbt_deadline_ms=tbt_deadline_ms)
    server = None
    if metrics_port is not None:
        server = MetricsServer(
            lambda: prometheus_text({arch: eng.metrics}),
            port=metrics_port)
        print(f"[serve.paged] metrics: {server.url}")
    try:
        done = eng.run()
    finally:
        if server is not None:
            server.close()
    return {"finished": done, "metrics": eng.metrics.snapshot()}


def serve_fleet(models, *, requests: int = 12, gen: int = 8,
                page_size: int = 16, total_pages: int = 64,
                max_seats: int = 4, prefill_chunk: int = 16,
                reduced: bool = True, seed: int = 0,
                eos_id: Optional[int] = None,
                sampling: Optional[SamplingParams] = None,
                prefix_cache: bool = True,
                max_seq_len: Optional[int] = None,
                prompt_len: Optional[int] = None,
                lazy_pages: bool = True, watermark: float = 0.05,
                priority: str = "standard",
                deadline_ms: Optional[float] = None,
                tbt_deadline_ms: Optional[float] = None,
                admission: str = "fcfs", aging_ticks: int = 64,
                selection: str = "least-loaded",
                kv_dtype: Optional[str] = None,
                class_precision: Optional[Dict[str, str]] = None,
                telemetry: Optional[Telemetry] = None,
                metrics_port: Optional[int] = None):
    """Drive a multi-model fleet over one mixed request stream.

    ``models`` is a ``--models``-style spec string
    (``llama3-8b:2:fp8,qwen3-1.7b``; module-style aliases like
    ``llama3_8b`` resolve too) or a pre-parsed
    [(name, replicas[, kv_dtype]), ...] list.  ``kv_dtype`` is the
    fleet-wide KV storage default for models whose spec entry leaves it
    unset; ``class_precision`` maps SLO classes to minimum precisions,
    steering those classes to replicas whose pool qualifies.  Every
    engine in the fleet shares one ``total_pages`` host budget —
    denominated in bytes when precisions are mixed, so quantized
    replicas' cheaper pages draw proportionally less; requests cycle
    across the models round-robin and rids are fleet-global, so
    per-request outputs match dedicated solo engines.  Returns the
    finished requests plus the fleet metrics snapshot (per-model
    tokens/s, TTFT, prefix hits, preemptions, SLO classes, budget
    accounting).

    ``telemetry`` attaches one shared observability plane (flight
    recorder tagged per ``model/replica`` engine, ``fleet_tick``
    heartbeat counters — docs/observability.md); ``metrics_port``
    serves per-replica Prometheus exposition during the run."""
    if isinstance(models, str):
        try:
            models = parse_models_spec(models)
        except ValueError as e:
            raise ValueError(f"--models: {e}") from None
    try:
        models = [(resolve_arch(m[0]), m[1],
                   m[2] if len(m) > 2 and m[2] is not None else kv_dtype)
                  for m in models]
    except KeyError as e:
        raise ValueError(f"--models: {e.args[0]}") from None
    if max_seq_len is None:
        max_seq_len = (prompt_len if prompt_len else 3 * page_size) + gen
    if prompt_len is not None and prompt_len + gen > max_seq_len:
        raise ValueError(
            f"--prompt-len {prompt_len} + --gen {gen} exceeds "
            f"--max-seq-len {max_seq_len}")
    if prompt_len is None and max_seq_len - gen < 2:
        raise ValueError(
            f"--max-seq-len {max_seq_len} leaves no room for prompts "
            f"after --gen {gen}; raise it or pass --prompt-len")
    entries = []
    for i, (name, reps, dt) in enumerate(models):
        cfg = get_config(name)
        if reduced:
            cfg = reduced_config(cfg)
        params = M.init_params(M.param_specs(cfg),
                               jax.random.PRNGKey(seed + i),
                               dtype=jnp.float32)
        entries.append(FleetModel(name, cfg, params, replicas=reps,
                                  kv_dtype=dt))
    fleet = ModelFleet(entries, total_pages=total_pages,
                       page_size=page_size, max_seats=max_seats,
                       max_seq_len=max_seq_len,
                       prefill_chunk=prefill_chunk, selection=selection,
                       prefix_cache=prefix_cache, lazy_pages=lazy_pages,
                       watermark=watermark, admission=admission,
                       aging_ticks=aging_ticks,
                       class_precision=class_precision,
                       telemetry=telemetry)
    rng = np.random.default_rng(seed)
    for i in range(requests):
        name = models[i % len(models)][0]
        cfg = fleet.group(name).cfg
        plen = (prompt_len if prompt_len
                else int(rng.integers(1, max_seq_len - gen)))
        fleet.submit(model=name,
                     prompt=rng.integers(0, cfg.vocab_size,
                                         plen).astype(np.int32),
                     max_new_tokens=int(rng.integers(2, gen + 1)),
                     eos_id=eos_id, sampling=sampling,
                     priority=priority, deadline_ms=deadline_ms,
                     tbt_deadline_ms=tbt_deadline_ms)
    server = None
    if metrics_port is not None:
        server = MetricsServer(
            lambda: prometheus_text(
                {f"{n}/{i}": e.metrics
                 for n, i, e in fleet._engines()}),
            port=metrics_port)
        print(f"[serve.fleet] metrics: {server.url}")
    try:
        done = fleet.run()
    finally:
        if server is not None:
            server.close()
    return {"finished": done, "metrics": fleet.metrics_snapshot()}


def add_sampling_args(ap: argparse.ArgumentParser) -> None:
    """Shared CLI sampling/termination flags (also used by examples)."""
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a request early on this token id")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax (default)")
    ap.add_argument("--top-k", type=int, default=0, help="0 = off")
    ap.add_argument("--top-p", type=float, default=1.0, help="1.0 = off")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed for params init and sampling streams")


def add_slo_args(ap: argparse.ArgumentParser) -> None:
    """Shared CLI SLO-class flags (also used by the examples): request
    priority/deadline plus the scheduler admission policy."""
    ap.add_argument("--priority", choices=("premium", "standard", "batch"),
                    default="standard",
                    help="SLO class applied to every submitted request")
    ap.add_argument("--tbt-deadline-ms", type=float, default=None,
                    help="per-decode-token deadline in ms: tightens EDF "
                         "rank to the next-token due time under "
                         "--admission slo, shields the request from "
                         "preemption within its class, and lands "
                         "tbt_p95_s / tbt_miss_rate in the metrics "
                         "snapshot")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="TTFT deadline per request in ms (EDF ordering "
                         "under --admission slo; misses are counted and "
                         "traced under either policy)")
    ap.add_argument("--admission", choices=("fcfs", "slo"), default="fcfs",
                    help="queue policy: fcfs (default) or slo = priority + "
                         "earliest-deadline-first with aging")
    ap.add_argument("--aging-ticks", type=int, default=64,
                    help="slo anti-starvation bound: a queued request "
                         "gains one priority class per this many ticks")


def parse_class_precision(spec: str) -> Dict[str, str]:
    """Parse a ``--class-precision`` map: comma-separated
    ``class=dtype`` entries, e.g. ``premium=bf16,standard=fp8``.
    Values must come from :data:`~repro.core.mixed_precision.KV_DTYPES`
    (deeper validation — class names, floor feasibility — happens in
    the engine/fleet constructors, which name the offending class).

    Raises:
      ValueError: malformed entry or an unknown dtype name."""
    out: Dict[str, str] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        cls, sep, dt = part.partition("=")
        cls, dt = cls.strip(), dt.strip()
        if not sep or not cls or not dt:
            raise ValueError(
                f"bad --class-precision entry {part!r}; expected "
                "class=dtype, e.g. premium=bf16,standard=fp8")
        if dt not in KV_DTYPES:
            raise ValueError(
                f"unknown kv dtype {dt!r} in --class-precision entry "
                f"{part!r}; expected one of {', '.join(KV_DTYPES)}")
        out[cls] = dt
    return out


def add_telemetry_args(ap: argparse.ArgumentParser) -> None:
    """Shared observability flags (paged engine and fleet modes) — see
    docs/observability.md for the workflows behind them."""
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve Prometheus text exposition of the live "
                         "engine metrics on 127.0.0.1:PORT for the "
                         "duration of the run (0 = ephemeral port, "
                         "printed at startup)")
    ap.add_argument("--flight-recorder", type=int, default=0,
                    metavar="N",
                    help="keep the last N structured trace events in a "
                         "ring buffer; a scheduler stall dumps them "
                         "plus a full engine-state snapshot as "
                         "postmortem JSON (0 = off unless another "
                         "telemetry flag turns telemetry on)")
    ap.add_argument("--trace-export", default=None, metavar="PATH",
                    help="after the run, write the recorded events as "
                         "Chrome trace-event JSON (open in "
                         "https://ui.perfetto.dev — one track per "
                         "engine seat)")
    ap.add_argument("--profile-ticks", action="store_true",
                    help="time the tick phases (admission / prefill / "
                         "decode, plus the fused tick's sync / dispatch "
                         "/ host / sample sub-phases) and print the "
                         "breakdown after the run")
    ap.add_argument("--postmortem", default=None, metavar="PATH",
                    help="where a stall postmortem JSON is written "
                         "(default: postmortem.json next to the run)")


def telemetry_from_args(args) -> Optional[Telemetry]:
    """Build one :class:`Telemetry` from ``add_telemetry_args`` flags,
    or None when every flag is at its off default (keeping the engines
    on the zero-overhead path)."""
    wanted = (args.flight_recorder or args.trace_export
              or args.profile_ticks or args.metrics_port is not None)
    if not wanted:
        return None
    return Telemetry(ring=args.flight_recorder or 4096,
                     profile=args.profile_ticks,
                     postmortem_path=args.postmortem or "postmortem.json")


def report_telemetry(args, telemetry: Optional[Telemetry],
                     tag: str) -> None:
    """Post-run telemetry outputs: the Perfetto export and the
    tick-phase profile table."""
    if telemetry is None:
        return
    rec = telemetry.recorder
    if args.trace_export:
        write_perfetto(args.trace_export, telemetry.events())
        print(f"[{tag}] wrote Perfetto trace {args.trace_export} "
              f"({rec.total} events recorded, {rec.dropped} aged out "
              f"of the {rec.capacity}-event ring)")
    if telemetry.profiler is not None:
        snap = telemetry.profiler.snapshot()
        print(f"[{tag}] tick-phase profile over {snap['ticks']} ticks:")
        for phase, ph in snap["phases"].items():
            print(f"[{tag}]   {phase:<16} {ph['total_s'] * 1e3:8.2f} ms "
                  f"total  {ph['share'] * 100:5.1f}%")


def add_kv_precision_args(ap: argparse.ArgumentParser) -> None:
    """Shared CLI KV-precision flags (paged engine and fleet)."""
    ap.add_argument("--kv-dtype", choices=KV_DTYPES, default=None,
                    help="KV pool storage precision; fp8/int8 quantize "
                         "pages with per-token scales for ~4x the tokens "
                         "per byte (default: the compute dtype). In "
                         "--fleet mode this is the default for models "
                         "whose --models entry has no :kv_dtype field")
    ap.add_argument("--class-precision", default=None,
                    help="SLO class -> minimum KV precision map, e.g. "
                         "premium=bf16,standard=fp8; requests of a "
                         "floored class only run on pools storing at "
                         "least that precision")


def sampling_from_args(args) -> SamplingParams:
    """Build :class:`SamplingParams` from ``add_sampling_args`` flags."""
    return SamplingParams(temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p, seed=args.seed)


def model_name(name: str) -> str:
    """argparse ``type=`` resolver for ``--model``/``--arch`` flags:
    canonicalizes registry ids and module-style aliases, and turns an
    unknown name into an argparse error that names the offending flag
    (``argument --model/--arch: ...``) and lists every known model."""
    try:
        return resolve_arch(name)
    except KeyError as e:
        raise argparse.ArgumentTypeError(e.args[0]) from None


def add_model_arg(ap: argparse.ArgumentParser,
                  default: str = "qwen3-1.7b") -> None:
    """Shared ``--model`` (alias ``--arch``) flag resolving through the
    config registry — also used by the serving examples."""
    ap.add_argument("--model", "--arch", dest="arch", type=model_name,
                    default=default,
                    help="registry model name (module-style aliases like "
                         f"llama3_8b work; default {default})")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=("batch", "paged"), default="batch")
    ap.add_argument("--fleet", action="store_true",
                    help="serve a multi-model fleet (--models) instead of "
                         "one engine; implies the paged engine")
    ap.add_argument("--models", default="qwen3-1.7b:2,llama3-8b",
                    help="fleet spec: comma-separated "
                         "name[:replicas[:kv_dtype]], e.g. "
                         "llama3-8b:2:fp8,qwen3-1.7b (--fleet mode)")
    ap.add_argument("--selection",
                    choices=("least-loaded", "round-robin", "slo-aware"),
                    default="least-loaded",
                    help="replica selection policy (--fleet mode); "
                         "slo-aware folds premium queue depth into the "
                         "least-loaded key")
    ap.add_argument("--total-pages", type=int, default=64,
                    help="shared host page budget across all fleet "
                         "engines (--fleet mode)")
    add_model_arg(ap)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=None,
                    help="fixed prompt length (batch default 32; the "
                         "paged engine samples lengths when unset)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=128)
    ap.add_argument("--max-seq-len", type=int, default=None,
                    help="per-request prompt+generation bound (paged; "
                         "default (prompt_len or 3*page_size) + gen)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable prefix-cache page sharing (paged engine)")
    ap.add_argument("--lazy-pages", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="allocate KV pages on demand during decode and "
                         "preempt under pressure (--no-lazy-pages restores "
                         "up-front full reservation)")
    ap.add_argument("--watermark", type=float, default=0.05,
                    help="lazy admission gate: free-page headroom kept at "
                         "admission, as a fraction of pool capacity")
    ap.add_argument("--tuning-preset", choices=("off", "alloc", "full"),
                    default="off",
                    help="host allocator / XLA environment preset: alloc "
                         "preloads tcmalloc; full adds XLA step-marker + "
                         "host-device-count flags (re-execs once to apply)")
    add_sampling_args(ap)
    add_slo_args(ap)
    add_kv_precision_args(ap)
    add_telemetry_args(ap)
    args = ap.parse_args()
    apply_tuning_preset(args.tuning_preset)
    enable_compile_cache()
    sampling = sampling_from_args(args)
    try:
        class_precision = (parse_class_precision(args.class_precision)
                           if args.class_precision else None)
    except ValueError as e:
        ap.error(str(e))
    telemetry = telemetry_from_args(args)
    if telemetry is not None and not args.fleet and args.engine != "paged":
        ap.error("--metrics-port/--flight-recorder/--trace-export/"
                 "--profile-ticks need --engine paged or --fleet (the "
                 "static batch path has no scheduler to observe)")
    if args.fleet:
        try:
            r = serve_fleet(args.models, requests=args.requests,
                            gen=args.gen, page_size=args.page_size,
                            total_pages=args.total_pages, seed=args.seed,
                            eos_id=args.eos_id, sampling=sampling,
                            prefix_cache=not args.no_prefix_cache,
                            max_seq_len=args.max_seq_len,
                            prompt_len=args.prompt_len,
                            lazy_pages=args.lazy_pages,
                            watermark=args.watermark,
                            priority=args.priority,
                            deadline_ms=args.deadline_ms,
                            tbt_deadline_ms=args.tbt_deadline_ms,
                            admission=args.admission,
                            aging_ticks=args.aging_ticks,
                            selection=args.selection,
                            kv_dtype=args.kv_dtype,
                            class_precision=class_precision,
                            telemetry=telemetry,
                            metrics_port=args.metrics_port)
        except ValueError as e:
            ap.error(str(e))
        m = r["metrics"]
        f = m["fleet"]
        print(f"[serve.fleet] {f['completed']:.0f} requests "
              f"{f['generated_tokens']:.0f} tokens in "
              f"{f['wall_s'] * 1e3:.0f}ms ({f['tokens_per_s']:.1f} tok/s) "
              f"across {len(m['models'])} models; "
              f"budget {m['budget']['total_pages']} pages "
              f"(surplus {m['budget']['surplus_pages']})")
        for name, mm in m["models"].items():
            print(f"[serve.fleet]   model={name} "
                  f"replicas={len(mm['replicas'])} "
                  f"completed={mm['completed']:.0f} "
                  f"tok/s={mm['tokens_per_s']:.1f} "
                  f"ttft_avg={mm['ttft_avg_s'] * 1e3:.0f}ms "
                  f"prefix_hit_rate={mm['prefix_hit_rate']:.2f} "
                  f"preemptions={mm['preemptions']:.0f}")
        rid0 = min(r["finished"])
        print("[serve.fleet] sample tokens:",
              r["finished"][rid0].generated[:12])
        report_telemetry(args, telemetry, "serve.fleet")
        return
    if args.engine == "paged":
        r = serve_paged(args.arch, requests=args.requests, gen=args.gen,
                        page_size=args.page_size, num_pages=args.num_pages,
                        seed=args.seed, eos_id=args.eos_id, sampling=sampling,
                        prefix_cache=not args.no_prefix_cache,
                        max_seq_len=args.max_seq_len,
                        prompt_len=args.prompt_len,
                        lazy_pages=args.lazy_pages, watermark=args.watermark,
                        priority=args.priority, deadline_ms=args.deadline_ms,
                        tbt_deadline_ms=args.tbt_deadline_ms,
                        admission=args.admission,
                        aging_ticks=args.aging_ticks,
                        kv_dtype=args.kv_dtype,
                        class_precision=class_precision,
                        telemetry=telemetry,
                        metrics_port=args.metrics_port)
        m = r["metrics"]
        print(f"[serve.paged] kv_dtype={m['kv_dtype']} "
              f"page_bytes={m['page_bytes']:.0f}")
        print(f"[serve.paged] {m['completed']:.0f} requests "
              f"{m['generated_tokens']:.0f} tokens in {m['wall_s'] * 1e3:.0f}ms "
              f"({m['tokens_per_s']:.1f} tok/s) "
              f"ttft_avg={m['ttft_avg_s'] * 1e3:.0f}ms "
              f"peak_page_util={m['peak_page_utilization']:.2f} "
              f"prefix_hit_rate={m['prefix_hit_rate']:.2f} "
              f"preemptions={m['preemptions']:.0f}")
        for cls, cm in m["classes"].items():
            print(f"[serve.paged]   class={cls} "
                  f"completed={cm['completed']:.0f} "
                  f"ttft_avg={cm['ttft_avg_s'] * 1e3:.0f}ms "
                  f"ttft_p95={cm['ttft_p95_s'] * 1e3:.0f}ms "
                  f"preemptions={cm['preemptions']:.0f} "
                  f"deadline_misses={cm['deadline_misses']:.0f}")
        print("[serve.paged] sample tokens:",
              r["finished"][0].generated[:12])
        report_telemetry(args, telemetry, "serve.paged")
        return
    r = serve(args.arch, batch=args.batch,
              prompt_len=args.prompt_len or 32,
              gen=args.gen, seed=args.seed, sampling=sampling)
    print(f"[serve] prefill={r['prefill_s'] * 1e3:.0f}ms "
          f"decode={r['decode_s'] * 1e3:.0f}ms "
          f"throughput={r['tokens_per_s']:.1f} tok/s")
    print("[serve] sample tokens:", r["generated"][0][:12].tolist())


if __name__ == "__main__":
    main()
