"""Traffic generator: one seeded request stream from a traffic-mix file.

A traffic mix is a JSON file under ``bench/traffic/`` that holds only
parameters.  This module is the one general generator that reads them.

Its length and gap arithmetic is the one of ``repro.runtime.workload``
(``_lognormal_int`` and the Gamma gaps of ``generate_workload``), copied
here so that the yardstick does not move when the program does:

* lengths: ``round(median * exp(sigma * z))`` clipped to ``[min, max]``
  (lognormal);
* gaps between arrivals: Gamma with shape ``1 / burstiness`` and scale
  ``burstiness`` (mean 1, squared CV ``burstiness``; 1 is Poisson),
  divided by the offered rate.

Where it departs from that module:

* the normal and Gamma variates are not independent draws but
  the midpoint quantiles of blocks of ``block`` requests, put in an
  order drawn once for the mix (``SCHEDULE_SEED``), not from the run's
  seed.  Every seed therefore serves exactly the same schedule of prompt
  lengths, output lengths and arrivals; the seed draws the token ids.  A
  tail such as the 90th percentile of some tens of first tokens moves by
  a third with the order of long prompts and short gaps (chip runs of
  the chat cell), so an order drawn from the seed would make the seed
  change the work;
* the replay kind (below) is its own;
* what the module has beside lengths and gaps is not copied: sessions
  and their think times, the diurnal envelope, the shared-prefix
  catalogue, request classes with deadlines, and sampled decoding.  A
  mix that needs one of them needs this generator extended first.

Kinds of mix:

* ``open_loop``: requests are due at the cumulative gaps, whatever the
  system does (independent users); the cell gives the rate.
* ``replay``: a fixed list of requests (``requests``: prompt and output
  lengths), all due at the start and none after them; for the longest
  contexts, where a window can hold only the decoding of a few.  The
  list's lengths are used as they stand; the seed draws the tokens.
"""
from __future__ import annotations

import dataclasses
import json
import math
from statistics import NormalDist
from typing import List

import numpy as np

KINDS = ("open_loop", "replay")
#: draws the order of each block's lengths and gaps, the same for every run
SCHEDULE_SEED = 1


@dataclasses.dataclass(frozen=True)
class Request:
    """One generated request: due ``due_s`` seconds after the traffic
    starts (0 in a replay), with its prompt and output budget."""
    index: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"{path}: kind must be one of {KINDS}, "
                         f"got {mix.get('kind')!r}")
    if mix["kind"] == "replay":
        if not mix.get("requests") or not all(
                int(r["prompt"]) > 0 and int(r["output"]) > 0
                for r in mix["requests"]):
            raise ValueError(f"{path}: a replay needs a list of requests "
                             f"with positive prompt and output lengths")
        return mix
    for key in ("prompt", "output"):
        if mix[key]["dist"] != "lognormal":
            raise ValueError(f"{path}: {key}.dist must be lognormal")
    return mix


def longest_total(mix: dict) -> int:
    """The largest prompt + output budget any request of ``mix`` has."""
    if mix["kind"] == "replay":
        return max(int(r["prompt"]) + int(r["output"])
                   for r in mix["requests"])
    return int(mix["prompt"]["max"]) + int(mix["output"]["max"])


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(dist: dict, n: int) -> np.ndarray:
    z = np.asarray([NormalDist().inv_cdf(float(x)) for x in _midpoints(n)])
    raw = [round(dist["median"] * math.exp(dist["sigma"] * float(v)))
           for v in z]
    return np.clip(np.asarray(raw, np.int64), int(dist["min"]),
                   int(dist["max"]))


def _gaps(burstiness: float, n: int) -> np.ndarray:
    """Gamma(1/b, b) midpoint quantiles, scaled so the block's mean gap
    is exactly 1."""
    u = _midpoints(n)
    if burstiness == 1.0:
        g = -np.log1p(-u)
    else:
        from scipy.special import gammaincinv
        g = gammaincinv(1.0 / burstiness, u) * burstiness
    return g / g.mean()


class Stream:
    """The request stream of one mix under one seed.

    ``rate`` (requests/s) is required for an ``open_loop`` mix.
    Request ``i`` is a pure function of (mix, seed, vocab, rate, i), and
    only its tokens depend on the seed.  ``size`` is the number of
    requests: a replay's list, else unbounded (None)."""

    def __init__(self, mix: dict, seed: int, vocab: int,
                 rate: float | None = None):
        self.mix = mix
        self.kind = mix["kind"]
        self.vocab = int(vocab)
        self.block = int(mix.get("block", 32))
        if self.kind == "open_loop":
            if not rate or rate <= 0:
                raise ValueError("an open_loop mix needs a rate > 0")
            self.rate = float(rate)
        self.seed = int(seed)
        self._blocks: List[tuple] = []
        self.size = None
        if self.kind == "replay":
            reqs = mix["requests"]
            self.size = self.block = len(reqs)
            self._blocks.append((
                np.asarray([int(r["prompt"]) for r in reqs]),
                np.asarray([int(r["output"]) for r in reqs]),
                np.zeros(len(reqs))))
            return
        self._prompt_set = _lengths(mix["prompt"], self.block)
        self._out_set = _lengths(mix["output"], self.block)
        self._gap_set = _gaps(float(mix.get("burstiness", 1.0)),
                              self.block)

    def _rng(self, *words: int) -> np.random.Generator:
        return np.random.default_rng(
            np.random.SeedSequence([self.seed % (1 << 64), *words]))

    def _block(self, b: int) -> tuple:
        while len(self._blocks) <= b:
            k = len(self._blocks)
            rng = np.random.default_rng(
                np.random.SeedSequence([SCHEDULE_SEED, k]))
            order_p = rng.permutation(self.block)
            order_o = rng.permutation(self.block)
            order_g = rng.permutation(self.block)
            self._blocks.append((self._prompt_set[order_p],
                                 self._out_set[order_o],
                                 self._gap_set[order_g]))
        return self._blocks[b]

    def due_s(self, i: int) -> float:
        """Seconds after the traffic starts at which request ``i`` is
        due: the sum of the gaps before it (open loop), else 0."""
        if self.kind != "open_loop":
            return 0.0
        b, j = divmod(i, self.block)
        # each whole block's gaps sum to exactly `block` mean gaps
        t = b * self.block + float(np.sum(self._block(b)[2][:j]))
        return t / self.rate

    def request(self, i: int) -> Request:
        if self.size is not None and not 0 <= i < self.size:
            raise IndexError(f"a replay of {self.size} has no request {i}")
        b, j = divmod(i, self.block)
        prompts, outs, _ = self._block(b)
        tokens = self._rng(2, i).integers(0, self.vocab, int(prompts[j]),
                                          dtype=np.int32)
        return Request(i, self.due_s(i), tokens, int(outs[j]))
