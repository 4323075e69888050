"""The trace reduction on a synthetic two-chip trace."""
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).with_name("data") / "two_chips.textproto"


@pytest.fixture(scope="module")
def summary():
    planes = tr.read_text_proto(DATA.read_text())
    return tr.reduce_trace(planes, kernels=("head_select", "msp_select"))


def test_window_and_busy(summary):
    assert summary.chips == 2
    assert summary.window_s == pytest.approx(0.020)
    # chip 0: [0,5) [6,10) [12,13) ms inside the window, the op at 25 ms
    # lies outside it; chip 1 is busy the whole window
    assert summary.busy_s == pytest.approx((0.010 + 0.020) / 2)
    assert summary.idle_share == pytest.approx(0.25)


def test_kernel_found_by_its_stats(summary):
    assert summary.kernel_s["head_select"] == pytest.approx(0.002)
    assert summary.kernel_calls["head_select"] == 1
    assert tr.kernel_time(summary, "head_select") == pytest.approx(0.002)
    assert tr.kernel_time(summary, "msp_select") is None


def test_collective_and_its_exposed_part(summary):
    assert summary.collective_s == pytest.approx(0.003)
    # the custom call covers [7, 8) ms of the all-reduce's [7, 10)
    assert summary.collective_exposed_s == pytest.approx(0.002)


def test_idle_gaps_carry_the_open_span(summary):
    assert summary.idle_gaps == [
        ("bench.eval", pytest.approx(0.007)),
        ("bench.eval", pytest.approx(0.002)),
        ("bench.round", pytest.approx(0.001))]


def test_top_device_ops(summary):
    ops = dict(summary.device_ops)
    assert ops["fusion.9"] == pytest.approx(0.020)
    assert ops["fusion.1"] == pytest.approx(0.005)
    assert summary.device_ops[0][0] == "fusion.9"


def test_trace_without_harness_span_is_refused():
    text = DATA.read_text().replace('"bench.', '"other.')
    with pytest.raises(ValueError, match="no harness span"):
        tr.reduce_trace(tr.read_text_proto(text))


def test_merge_and_subtract():
    assert tr._merge([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert tr._subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
