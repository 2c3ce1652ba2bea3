"""The general traffic generator: deterministic per seed, the same sizes and
arrivals for every seed, Poisson arrivals with their bursts, and the mix's
length and rate parameters kept."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import mixgen

MIXES = Path(__file__).resolve().parents[2] / "bench" / "traffic"
UNIFORM = {"arrivals": "poisson",
           "prompt_len": {"dist": "uniform", "min": 1536, "max": 3584},
           "output_len": {"dist": "uniform", "min": 16, "max": 64}}


def mix(name):
    if name == "uniform":
        return UNIFORM
    return json.loads((MIXES / f"{name}.json").read_text())


def sig(items):
    return [(it.due_s, len(it.prompt), it.max_new, it.prompt[:4].tolist())
            for it in items]


@pytest.mark.parametrize("name,rate", [("chat", 7.5), ("chat", 0.55),
                                       ("uniform", 3.0)])
def test_deterministic_per_seed(name, rate):
    load = {"rate_per_s": rate}
    a = mixgen.generate(mix(name), load, 2**31 + 77, 20, 1000)
    b = mixgen.generate(mix(name), load, 2**31 + 77, 20, 1000)
    c = mixgen.generate(mix(name), load, 2**31 + 78, 20, 1000)
    assert sig(a) == sig(b)
    assert sig(a) != sig(c)


@pytest.mark.parametrize("name", ["chat", "uniform"])
def test_same_work_every_seed(name):
    m = mix(name)
    runs = [mixgen.generate(m, {"rate_per_s": 6.0}, s, 30, 500)
            for s in (1, 2, 3**20)]
    for s in runs:
        assert len(s) == 180
        due = [it.due_s for it in s]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 30
        p = np.array([len(it.prompt) for it in s])
        o = np.array([it.max_new for it in s])
        assert p.min() >= m["prompt_len"]["min"]
        assert p.max() <= m["prompt_len"]["max"]
        assert o.min() >= m["output_len"]["min"]
        assert o.max() <= m["output_len"]["max"]
        assert all(0 <= t < 500 for it in s for t in it.prompt[:8])
    sizes = [sorted(len(it.prompt) for it in s) for s in runs]
    assert sizes[0] == sizes[1] == sizes[2]
    due = [[it.due_s for it in s] for s in runs]
    assert due[0] == due[1] == due[2]        # one arrival pattern


def test_arrivals_are_poisson_with_its_bursts():
    n, seconds = 4000, 2000.0
    t = mixgen.poisson_arrivals(n, seconds, np.random.default_rng(5))
    assert len(t) == n and np.all(np.diff(t) >= 0) and t[-1] < seconds
    gaps = np.diff(t) / (seconds / (n + 1))      # in units of the mean gap
    assert abs(gaps.mean() - 1) < 0.05
    assert abs(gaps.std() - 1) < 0.08            # exponential: sd = mean
    assert abs(np.mean(gaps < 0.25) - (1 - np.exp(-0.25))) < 0.03
    # runs of 3 arrivals within one mean gap: p = 1 - e^-1 (1 + 1) per pair
    p3 = 1 - 2 * np.exp(-1)
    burst3 = np.mean(gaps[:-1] + gaps[1:] < 1)
    assert abs(burst3 - p3) < 0.03
    # the chat cell's own schedule keeps such bursts
    due = np.array([it.due_s for it in mixgen.generate(
        mix("chat"), {"rate_per_s": 2.0}, 7, 60, 100)])
    g = np.diff(due) * 2.0
    assert np.sum(g[:-1] + g[1:] < 1) >= 5


def test_unknown_arrivals_are_refused():
    m = dict(mix("chat"), arrivals="onoff")
    with pytest.raises(ValueError, match="bench/traffic/<mix>.py"):
        mixgen.generate(m, {"rate_per_s": 1.0}, 1, 10, 100)


def test_every_stretch_holds_the_same_mix():
    m = mix("chat")
    s = mixgen.generate(m, {"rate_per_s": 5.2}, 11, 30, 500)
    b = mixgen.BLOCK
    ramp = sum(it.due_s < m["ramp_s"] for it in s)
    assert (len(s), ramp) == (156, 52)       # both parts whole runs of b
    for part in (s[:ramp], s[ramp:]):        # the ramp, then the window
        n = len(part)
        lens = np.array([len(it.prompt) for it in part])
        strata = np.sort(lens).reshape(b, n // b)
        for i in range(0, n, b):
            run = np.sort(lens[i:i + b])
            # one value from each stratum of the part's sorted lengths
            assert all(strata[j, 0] <= run[j] <= strata[j, -1]
                       for j in range(b))


@pytest.mark.parametrize("rate", [0.55, 2.0])
def test_window_holds_the_same_work_every_seed(rate):
    """The requests due after the ramp hold one multiset of lengths, the
    same for every seed, and so do those due in it: a seed cannot move the
    longest prompts out of the measured window."""
    m = mix("chat")
    runs = [mixgen.generate(m, {"rate_per_s": rate}, s, 60, 500)
            for s in (1, 2, 2**31 + 9, 4315001507)]

    def part(s, in_window):
        its = [it for it in s if (it.due_s >= m["ramp_s"]) == in_window]
        return (sorted(len(it.prompt) for it in its),
                sorted(it.max_new for it in its))
    for in_window in (False, True):
        got = [part(s, in_window) for s in runs]
        assert all(g == got[0] for g in got)
    window = part(runs[0], True)
    for lens, key in zip(window, ("prompt_len", "output_len")):
        assert lens == list(mixgen.quantile_lengths(m[key], len(lens)))
    assert window[0][-1] == m["prompt_len"]["max"]
    orders = {tuple(len(it.prompt) for it in s) for s in runs}
    assert len(orders) == len(runs)          # the seed changes the order


def test_lognormal_median_and_uniform_range():
    ln = mixgen.quantile_lengths(mix("chat")["prompt_len"], 1001)
    assert abs(np.median(ln) - 400) <= 1
    un = mixgen.quantile_lengths(UNIFORM["prompt_len"], 1000)
    assert un.min() >= 1536 and un.max() <= 3584
    assert abs(un.mean() - (1536 + 3584) / 2) < 3


def test_seeds_beyond_32_bits_differ():
    from bench import weights
    a, b = weights.seed_key(5), weights.seed_key(5 + 2**32)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
