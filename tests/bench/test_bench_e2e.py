"""Tail and rate arithmetic: backlog counts with its wait so far, a failed
request is infinitely late, percentiles are nearest-rank."""
import math
from types import SimpleNamespace

import pytest

from bench import e2e


def req(due, stamps, status="done"):
    return SimpleNamespace(due=due, stamps=stamps, status=status)


def rec(requests, t0=0.0, t1=10.0):
    return SimpleNamespace(requests=requests, t0=t0, t1=t1,
                           window_s=t1 - t0)


def test_nearest_rank():
    xs = list(range(1, 21))
    assert e2e.nearest_rank(xs, 95) == 19
    assert e2e.nearest_rank(xs, 100) == 20
    assert e2e.nearest_rank([5.0], 95) == 5.0
    assert math.isnan(e2e.nearest_rank([], 95))


def test_ttft_counts_backlog_with_its_wait_so_far():
    rs = [req(1.0, [1.5, 1.6]),          # served: 0.5 s
          req(2.0, [12.0]),             # first token after the close
          req(9.0, []),                 # still waiting at the close
          req(-1.0, [0.5])]             # due before the window opened
    assert e2e.ttfts_s(rs, 0.0, 10.0) == pytest.approx([0.5, 8.0, 1.0])


def test_failed_request_is_infinitely_late():
    rs = [req(0.0, [0.1])] * 19 + [req(0.0, [], status="rejected")]
    assert e2e.ttfts_s(rs, 0.0, 10.0)[-1] == math.inf
    m = e2e.metrics(rec(rs))
    assert m["ttft_p95_ms"] == pytest.approx(100.0)   # rank 19 of 20
    rs2 = rs[:18] + [req(0.0, [], status="expired")] * 2
    assert e2e.metrics(rec(rs2))["ttft_p95_ms"] == e2e.TOO_LATE_MS
    assert e2e.failed(rs2) == 2


def test_itl_and_tokens_inside_the_window():
    rs = [req(0.0, [1.0, 1.1, 1.3, 10.5]), req(5.0, [6.0, 6.4]),
          req(-3.0, [-1.0, 0.5, 0.7])]  # sent in the ramp: 0.5 and 0.7 count
    assert sorted(e2e.itls_s(rs, 0.0, 10.0)) == pytest.approx(
        [0.1, 0.2, 0.2, 0.4])
    m = e2e.metrics(rec(rs))
    assert m["output_tok_s"] == pytest.approx(7 / 10.0)
    assert m["itl_p95_ms"] == pytest.approx(400.0)
