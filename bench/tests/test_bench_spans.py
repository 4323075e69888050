"""Chip 0's idle time split by the program's spans, on a synthetic trace:
``two_chips.textproto`` with ``idkd.*`` spans added on the host."""
from pathlib import Path

import pytest

from bench import harness, spans
from bench import trace as tr

DATA = Path(__file__).with_name("data")
FIELDS = ("chips", "window_s", "busy_s", "kernel_s", "kernel_calls",
          "collective_s", "collective_exposed_s", "device_ops", "idle_gaps")
READERS = ("device_idle_share.round", "round_mfu", "head_select_roofline")


def _planes(name):
    return tr.read_text_proto((DATA / name).read_text())


@pytest.fixture(scope="module")
def split():
    return spans.split_idle(_planes("two_chips_idkd.textproto"))


@pytest.fixture(scope="module")
def summaries():
    return {name: tr.reduce_trace(_planes(name),
                                  kernels=("head_select", "msp_select"))
            for name in ("two_chips.textproto", "two_chips_idkd.textproto")}


def test_split_sums_to_chip0_idle(split):
    # chip 0 is idle at [6,7) [11,13) [14,21) ms of the window [1,21) ms
    assert split.idle_s == pytest.approx(0.010)
    assert sum(split.span_idle_s.values()) == pytest.approx(split.idle_s)


def test_innermost_span_takes_each_piece(split):
    assert split.span_idle_s == pytest.approx({
        "idkd.round": 0.0005,           # [6, 6.5)
        "idkd.public_pass": 0.0005,     # [6.5, 7), inside idkd.round
        "idkd.readback": 0.0005,        # [11, 11.5)
        "bench.round": 0.0005,          # [11.5, 12), after idkd.round
        "bench.eval": 0.006,            # [12, 13) [14, 15) [17, 21)
        "idkd.topk_overlap": 0.0015,    # [15, 15.5) [16, 17)
        "idkd.exchange": 0.0005})       # [15.5, 16), inside topk_overlap


def test_host_seconds_by_span_in_the_window(split):
    assert split.span_s == pytest.approx({
        "bench.round": 0.011, "bench.eval": 0.009, "idkd.round": 0.010,
        "idkd.public_pass": 0.001, "idkd.readback": 0.001,
        "idkd.topk_overlap": 0.002, "idkd.exchange": 0.0005})


def test_idle_gaps_carry_program_names(split):
    assert split.idle_gaps == [
        ("bench.eval", pytest.approx(0.007)),
        ("bench.eval", pytest.approx(0.002)),
        ("idkd.public_pass", pytest.approx(0.001))]


def test_round_phases(split):
    rounds = 2
    out = spans.round_phases(split, rounds, [
        {"compile_path_s": 0.5, "compiles": 2, "cache_hits": 2},
        {"compile_path_s": 0.3, "compiles": 2, "cache_hits": 1}])
    dispatch, host = out["round_idle_s.dispatch"], out["round_idle_s.host"]
    assert dispatch["value"] == pytest.approx(0.0005 / rounds)
    assert dispatch["idkd.calibration_pass"] == 0.0
    assert host["value"] == pytest.approx(0.003 / rounds)
    assert host["unattributed_s"] == pytest.approx(0.0065 / rounds)
    assert (dispatch["value"] + host["value"] + host["unattributed_s"]
            == pytest.approx(split.idle_s / rounds))
    assert out["round_compile_path_s"] == {
        "value": pytest.approx(0.4), "compiles": 4, "cache_hits": 3}
    assert "round_compile_path_s" not in spans.round_phases(split, rounds)


@pytest.mark.parametrize("field", FIELDS)
def test_program_spans_leave_the_trace_reduction_as_it_was(summaries, field):
    old, new = summaries.values()
    assert getattr(new, field) == getattr(old, field)


@pytest.mark.parametrize("metric", READERS)
def test_program_spans_leave_the_device_metrics_as_they_were(summaries,
                                                             metric):
    def read(summary):
        win = harness.Window(seconds=0.02, units=2, work={"rounds": 2})
        ctx = harness.Context(
            setup_s=1.0, window=win, chips=2,
            peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
            flops={"round": 1e9,
                   "head_select": {"flops": 1e8, "bytes": 1e6}},
            trace=summary, traced=win)
        return harness.load_reader(metric)(ctx)

    old, new = summaries.values()
    assert read(old) is not None
    assert read(new) == read(old)


def test_a_trace_without_the_window_is_refused():
    text = (DATA / "two_chips_idkd.textproto").read_text().replace(
        '"bench.window"', '"bench.other"')
    with pytest.raises(ValueError, match="bench.window"):
        spans.split_idle(tr.read_text_proto(text))
