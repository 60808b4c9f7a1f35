"""Which device work belongs to which layer of the serving path, read
from a normalised trace (see ``tracereduce``).

* kernels: the paged decode attention kernel, by its op name;
* model step: the runs of the fused decode tick program (the program
  runs that hold the kernel) and of the prefill program (runs of the
  same jitted step without the kernel: chunked prefill takes the
  gather path).
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

import tracereduce
import work

#: the paged decode attention kernel as the trace names it
KERNEL = r"paged_decode_attention"


def _base(name: str) -> str:
    return re.sub(r"(\(\d+\)|\.\d+)+$", "", name)


def program_runs(run) -> Optional[Tuple[List[list], List[list]]]:
    """(decode tick runs, prefill runs) inside the traced window, or
    None when the run was not traced or the trace holds no tick."""
    tw = run.trace_window() if run.trace is not None else None
    if tw is None:
        return None
    decode, rest = tracereduce.modules_holding(run.trace, KERNEL, *tw)
    if not decode:
        return None
    base = {_base(m[0]) for m in decode}
    prefill = [m for m in rest if _base(m[0]) in base]
    return decode, prefill


def seconds(runs: List[list]) -> float:
    return sum(m[2] for m in runs) / 1e9


# readers shared by the .chat and .batch metrics of one quantity

def decode_tick_ms(run):
    """Model step: device time of the fused decode tick program in the
    traced window, over its runs."""
    runs = program_runs(run)
    if runs is None:
        return None
    return seconds(runs[0]) / len(runs[0]) * 1e3


def paged_attn_roofline(run):
    """Kernels: the paged decode attention kernel's share of its roofline.

    The least time the chip needs for the attention the window's ticks
    asked for (live lengths only; ``bench/work.py``), over the kernel's
    device time in the trace, in %."""
    tw = run.trace_window() if run.trace is not None else None
    if tw is None:
        return None
    spent = tracereduce.op_time_s(run.trace, KERNEL, *tw)
    ticks = [s.decode_lens for s in run.steps if s.decode_lens]
    if spent <= 0 or not ticks:
        return None
    need = work.paged_attention_min_s(run.model, ticks,
                                      run.peaks["bf16_flops_per_s"],
                                      run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / spent


def decode_mfu(run):
    """Whole decode step: model FLOPs the window's ticks required (2 per
    matmul parameter per decoding seat, plus attention over live lengths;
    ``bench/work.py``) over the fused tick's device time times the chip's
    bf16 peak, in %."""
    runs = program_runs(run)
    ticks = [s.decode_lens for s in run.steps if s.decode_lens]
    if runs is None or not ticks:
        return None
    flops = sum(work.decode_step_flops(run.model, t) for t in ticks)
    return 100.0 * flops / (seconds(runs[0])
                            * run.peaks["bf16_flops_per_s"])


def device_idle_share(run):
    """Device: share of the traced window in which no operation ran on the
    device (1 - union of op intervals / window), in %."""
    tw = run.trace_window() if run.trace is not None else None
    if tw is None:
        return None
    busy = tracereduce.busy_s(run.trace, *tw)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ((tw[1] - tw[0]) / 1e9))
