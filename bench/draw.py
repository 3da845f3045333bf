"""Seeded draws for the traffic generators.

Every quantity is drawn STRATIFIED: a block of ``n`` values sits at the
quantiles (i + 0.5) / n of its distribution and only their order comes
from the seed.  So every seed sends the same multiset of lengths, turn
counts and gaps, in another order, and two seeds differ in which request
meets which, not in how much work there is.  Token ids are plain seeded
draws.

A distribution is a small dict, as written in a traffic file:

  {"lognormal": {"median": 96, "sigma": 1.2}, "min": 8, "max": 4096}
  {"loguniform": {"min": 2048, "max": 8192}}
  {"geometric": {"mean": 2.5}, "max": 8}
  {"exponential": {"mean": 4.0}}
  {"zipf": {"n": 8, "a": 1.2}}              (ranks 0..n-1)
  {"mixture": [{"share": 0.8, ...dist}, {"share": 0.2, ...dist}]}
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

_NORMAL = NormalDist()


def quantile(dist: Dict, u: float) -> float:
    """The value of ``dist`` at quantile ``u`` in (0, 1), clipped to its
    optional ``min`` / ``max``."""
    if "lognormal" in dist:
        p = dist["lognormal"]
        x = p["median"] * math.exp(p["sigma"] * _NORMAL.inv_cdf(u))
    elif "loguniform" in dist:
        p = dist["loguniform"]
        lo, hi = math.log(p["min"]), math.log(p["max"])
        x = math.exp(lo + u * (hi - lo))
    elif "geometric" in dist:
        q = 1.0 - 1.0 / dist["geometric"]["mean"]
        x = 1.0 if q <= 0 else max(1.0, math.ceil(math.log1p(-u)
                                                  / math.log(q)))
    elif "exponential" in dist:
        x = -dist["exponential"]["mean"] * math.log1p(-u)
    elif "zipf" in dist:
        p = dist["zipf"]
        w = np.arange(1, p["n"] + 1, dtype=np.float64) ** -p["a"]
        cdf = np.cumsum(w) / w.sum()
        x = float(min(int(np.searchsorted(cdf, u, side="right")),
                      p["n"] - 1))
    elif "mixture" in dist:
        edge = 0.0
        parts = dist["mixture"]
        for i, part in enumerate(parts):
            share = part["share"]
            if u < edge + share or i == len(parts) - 1:
                v = min(max((u - edge) / share, 1e-9), 1 - 1e-9)
                return quantile(part, v)
            edge += share
        raise AssertionError("unreachable")
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "min" in dist:
        x = max(x, dist["min"])
    if "max" in dist:
        x = min(x, dist["max"])
    return x


def stratified(dist: Dict, n: int, rng: np.random.Generator) -> List[float]:
    """``n`` values at the quantiles (i + 0.5) / n, in seeded order."""
    vals = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    order = rng.permutation(n)
    return [vals[i] for i in order]


STRATA = 32


class Stream:
    """Endless stratified draws: block after block of ``block`` values,
    each block the same multiset, each shuffled by the seed.  A small
    block keeps the work of any stretch of a run alike across seeds; a
    traffic file sets it as ``strata``."""

    def __init__(self, dist: Dict, rng: np.random.Generator,
                 block: int = STRATA, integer: bool = False):
        self.dist, self.rng, self.block = dist, rng, block
        self.integer = integer
        self._buf: List[float] = []

    def next(self):
        if not self._buf:
            self._buf = stratified(self.dist, self.block, self.rng)[::-1]
        x = self._buf.pop()
        return int(round(x)) if self.integer else x


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per named stream of one seed, so adding
    a stream to a kind never shifts the draws of another."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF]
    words += [ord(c) for c in stream]
    return np.random.default_rng(words)


def tokens(rng: np.random.Generator, vocab: int, n: int) -> np.ndarray:
    return rng.integers(0, vocab, int(n)).astype(np.int32)
