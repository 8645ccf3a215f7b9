"""The one traffic generator: it reads a mix's parameters and the run's seed.

Lengths are stratified quantiles of the mix's stated distribution, so a
mix matches its distribution exactly.  A closed-loop mix lays its
lengths out once (``schedule_seed``), as a fixed schedule per client, and
every run replays that schedule; the run's seed draws every token id (and
the harness draws the weights from it).  The work a window holds is then
the same from seed to seed -- a window completes only a handful of these
long requests, and a median over a handful of different requests would
swing with the draw -- while what the model computes changes with the
seed.

A mix with ``"stagger": true`` starts client ``c`` of ``C`` part-way
through its first answer, ``(c + 0.5) / C`` of the way, as a loop that
has run a while would find it: the first answers end spread out, and the
window holds arrivals, prefill chunks and first tokens from its start.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

__all__ = ["quantiles", "Request", "closed_loop"]


def quantiles(dist: Dict, n: int) -> List[int]:
    """``n`` stratified draws of a length distribution, ascending:
    ``{"dist": "lognormal", "median", "sigma", "lo", "hi"}`` or
    ``{"dist": "uniform", "lo", "hi"}`` (both bounds inclusive)."""
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if not 1 <= lo <= hi:
        raise ValueError(f"bad length bounds {lo}..{hi}")
    ps = [(i + 0.5) / n for i in range(n)]
    if dist["dist"] == "lognormal":
        mu, sd = math.log(dist["median"]), float(dist["sigma"])
        xs = [math.exp(mu + sd * NormalDist().inv_cdf(p)) for p in ps]
    elif dist["dist"] == "uniform":
        xs = [lo + p * (hi + 1 - lo) - 0.5 for p in ps]
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return [min(hi, max(lo, int(round(x)))) for x in xs]


@dataclasses.dataclass(frozen=True)
class Request:
    client: int
    index: int                 # the client's k-th request
    prompt: np.ndarray         # int32 token ids
    out_len: int               # tokens the client reads before it stops


def _schedule(mix: Dict) -> List[List[tuple]]:
    """Per client, its (prompt_len, out_len) sequence; fixed per mix."""
    C, R = int(mix["clients"]), int(mix["rounds"])
    rng = np.random.default_rng(int(mix["schedule_seed"]))
    plens = rng.permutation(quantiles(mix["prompt"], C * R))
    olens = rng.permutation(quantiles(mix["output"], C * R))
    return [[(int(plens[k * C + c]), int(olens[k * C + c]))
             for k in range(R)] for c in range(C)]


class closed_loop:
    """A closed loop of ``clients``, started in client order: each
    client's next request is due when its previous one ends.  ``next(c)``
    gives client ``c``'s next request; a client that has run its whole
    schedule starts it again with new prompts."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, int(seed), int(vocab)
        self.schedule = _schedule(mix)
        self._k = [0] * len(self.schedule)

    @property
    def clients(self) -> int:
        return len(self.schedule)

    def _lengths(self, c: int, k: int) -> tuple:
        plen, olen = self.schedule[c][k % len(self.schedule[c])]
        if k == 0 and self.mix.get("stagger"):
            C = self.clients
            olen = max(1, math.ceil(olen * (C - c - 0.5) / C))
        return plen, olen

    def freed_blocks(self, block_size: int) -> List[int]:
        """Every count of cache blocks that ending one of the loop's
        requests at its drawn length releases: its prompt and all its
        answer tokens but the last, which is never written back."""
        R = len(self.schedule[0])
        return sorted({-(-(p + o - 1) // block_size)
                       for c in range(self.clients) for k in range(R + 1)
                       for p, o in [self._lengths(c, k)]})

    def next(self, c: int) -> Request:
        k = self._k[c]
        self._k[c] += 1
        plen, olen = self._lengths(c, k)
        rng = np.random.default_rng((self.seed, 3, c, k))
        prompt = rng.integers(0, self.vocab, plen, dtype=np.int32)
        return Request(c, k, prompt, olen)
