"""``bench/trace.py`` on a small trace recorded on a TPU v5e by
``data/record_small_trace.py``: two jitted programs (a 2048^2 bf16 matmul
with a reduction, then a tanh) run three times, with a 2 ms host sleep
between, inside the host span ``bench.traced_window``.  The numbers below were read off the trace's
events by hand.  Host and device clocks in it differ by about 1 ms, so the
first round's device ops fall before the window and are left out."""
from pathlib import Path

import pytest

from bench import trace

XPLANE = str(Path(__file__).parent / "data" / "v5e_small.xplane.pb")
NS = 1e-9


@pytest.fixture(scope="module")
def red():
    return trace.reduce(XPLANE, "bench.traced_window",
                        host_spans=("step", "host.sleep"))


def test_window_and_busy(red):
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(12962999 * NS, abs=1e-12)
    # tanh 25581, then copy-start 13, copy-done 11650 and the matmul 90086
    # (2 and 1 ns apart), tanh 25647, then 13 + 11690 + 90086, tanh 25303
    busy = 25581 + 101749 + 25647 + 101789 + 25303
    assert red["busy_s"] == pytest.approx(busy * NS, abs=3e-9)


def test_per_op_seconds(red):
    ops = red["op_s"]
    assert ops["convert_reduce_fusion"] == pytest.approx(2 * 90086 * NS)
    assert ops["tanh_multiply_fusion"] == pytest.approx(
        (25581 + 25647 + 25303) * NS)
    assert ops["copy-done"] == pytest.approx((11650 + 11690) * NS)
    assert trace.op_seconds(red, r"^tanh") == pytest.approx(
        ops["tanh_multiply_fusion"])
    assert trace.top(ops, 1)[0][0] == "convert_reduce_fusion"


def test_idle_gaps_charged_to_host_spans(red):
    idle = red["idle_s"]
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    assert idle["host.sleep"] > 3e-3          # two 2 ms sleeps, mostly idle
    assert set(idle) <= {"step", "host.sleep", "other"}


def test_op_name_and_union():
    assert trace.op_name("%fusion.12 = bf16[2]{0} fusion(%a)") == "fusion.12"
    assert trace._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
