"""The general traffic generator: a mix file's parameters and a seed in, the
open-loop schedule of one run out.

A mix file (``bench/traffic/<mix>.json``) states ``arrivals`` (the arrival
process), ``prompt_len`` and ``output_len`` (length distributions) and
``ramp_s``; the cell states the rate.  A mix that these parameters cannot
describe brings its own generator as ``bench/traffic/<mix>.py`` (see
``bench/spec.py``).

Every seed offers the same work.  The arrival times are one realization of
the arrival process, the same for every seed, so its bursts and lulls are
those of real traffic and identical in every run.  Lengths are the mix's
distribution read at evenly spaced quantiles, the same multiset for every
seed, in a seed-dependent order that is stratified: the sorted values are
cut into ``BLOCK`` strata, and every run of ``BLOCK`` consecutive requests
takes one value from each stratum, shuffled.  The requests due in the
mix's ``ramp_s`` and those due after it (the measured window) each take a
multiset of their own, so the window holds the same lengths whatever the
seed: a tail read over a few dozen requests (a p95 is then one of the
largest two) rests on the same longest prompts in every run.  The seed
changes which request of a stretch comes first, and which tokens each one
holds.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

BLOCK = 4           # requests per stratified stretch of a schedule
ARRIVAL_SEED = 0    # the one realization of the arrival process


@dataclasses.dataclass
class Item:
    due_s: float               # seconds after the schedule starts
    prompt: np.ndarray         # (S,) int32 token ids
    max_new: int


def poisson_arrivals(n: int, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Arrival times of a Poisson process given ``n`` arrivals in
    ``[0, seconds)``: the partial sums of ``n + 1`` independent exponential
    gaps, scaled so that all ``n + 1`` span ``seconds``."""
    c = np.cumsum(rng.exponential(size=n + 1))
    return seconds * c[:-1] / c[-1]


ARRIVALS = {"poisson": poisson_arrivals}


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the (k + 0.5) / n quantiles of ``dist``: lognormal
    (``median``, ``sigma``) or uniform, clipped to [``min``, ``max``]."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def stratified(values: np.ndarray, rng: np.random.Generator,
               block: int = BLOCK) -> np.ndarray:
    """``values`` reordered into runs that each hold at most one value from
    each of ``block`` strata of the sorted values, each run shuffled; where
    ``block`` divides ``len(values)``, every run of ``block`` consecutive
    entries holds exactly one from each stratum."""
    n = len(values)
    m = -(-n // block)                         # runs
    v = np.sort(values)
    idx = np.linspace(0, n, block + 1).astype(int)
    runs = [[] for _ in range(m)]
    for j in range(block):
        stratum = list(rng.permutation(v[idx[j]:idx[j + 1]]))
        for i in rng.permutation(m)[:len(stratum)]:
            runs[i].append(stratum.pop())
    return np.concatenate([rng.permutation(r) for r in runs])


def generate(mix: dict, load: dict, seed: int, seconds: float,
             vocab: int) -> list:
    """The requests of one run, by due time: ``round(rate_per_s *
    seconds)`` of them (``load`` is the cell's offered load), due over
    ``seconds`` however fast they are served; those due in the first
    ``ramp_s`` and the rest take their lengths apart."""
    if mix["arrivals"] not in ARRIVALS:
        raise ValueError(
            f"unknown arrivals {mix['arrivals']!r}: one of {sorted(ARRIVALS)}"
            ", or a generator of the mix's own in bench/traffic/<mix>.py")
    n = max(1, int(round(load["rate_per_s"] * seconds)))
    rng = np.random.default_rng(seed)
    due = ARRIVALS[mix["arrivals"]](n, seconds,
                                    np.random.default_rng(ARRIVAL_SEED))
    ramp = int(np.searchsorted(due, mix.get("ramp_s", 0.0)))
    parts = [m for m in (ramp, n - ramp) if m]

    def lengths(dist):
        return np.concatenate([stratified(quantile_lengths(dist, m), rng)
                               for m in parts])
    p = lengths(mix["prompt_len"])
    o = lengths(mix["output_len"])
    return [Item(float(d), rng.integers(0, vocab, int(a), dtype=np.int32),
                 int(b)) for d, a, b in zip(due, p, o)]
