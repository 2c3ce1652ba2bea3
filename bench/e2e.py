"""End-to-end metrics of one window, from the host-clock stamps.

* ``ttft_p95_ms``: 95th percentile over every request due in the window,
  from its due time to its first token.  A request with no first token by
  the window's close counts with its wait so far; a rejected or expired one
  counts as missing every limit (infinitely late).
* ``itl_p95_ms``: 95th percentile over every gap between consecutive output
  tokens of one request, both inside the window (requests sent before the
  window opened count too).
* ``output_tok_s``: output tokens stamped inside the window over its length.

Percentiles are nearest-rank, so an infinitely late request shows as soon as
it falls in the top 5% and never turns the tail into a blend.
"""
from __future__ import annotations

import math

FAILED = ("rejected", "expired")
TOO_LATE_MS = 1e9          # printed for a tail that a failed request holds


def nearest_rank(values, q: float) -> float:
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def ttfts_s(requests, t0: float, t1: float) -> list:
    """Every request due in [t0, t1)."""
    out = []
    for r in requests:
        if not t0 <= r.due < t1:
            continue
        if r.status in FAILED:
            out.append(math.inf)
        elif r.stamps and r.stamps[0] <= t1:
            out.append(r.stamps[0] - r.due)
        else:
            out.append(t1 - r.due)
    return out


def itls_s(requests, t0: float, t1: float) -> list:
    gaps = []
    for r in requests:
        st = [t for t in r.stamps if t0 <= t <= t1]
        gaps += [b - a for a, b in zip(st, st[1:])]
    return gaps


def output_tokens(requests, t0: float, t1: float) -> int:
    return sum(t0 <= t <= t1 for r in requests for t in r.stamps)


def _ms(x: float) -> float:
    return TOO_LATE_MS if math.isinf(x) else x * 1e3


def metrics(rec) -> dict:
    """Every end-to-end metric but ``setup_s``, by name."""
    return {
        "ttft_p95_ms": _ms(nearest_rank(
            ttfts_s(rec.requests, rec.t0, rec.t1), 95)),
        "itl_p95_ms": _ms(nearest_rank(
            itls_s(rec.requests, rec.t0, rec.t1), 95)),
        "output_tok_s": output_tokens(rec.requests, rec.t0, rec.t1)
        / rec.window_s,
    }


def failed(requests) -> int:
    return sum(r.status in FAILED for r in requests)
