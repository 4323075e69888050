"""The threshold comparison and the roofline reader, on numbers made up
for the purpose (no model, no chip)."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import checks, harness, trace


def scores(seed: int):
    rng = np.random.default_rng(seed)
    return rng.random(16), rng.random(64)


@pytest.mark.parametrize("seed", range(4))
def test_the_sweeps_own_threshold_reads_nought(seed):
    ids, oods = scores(seed)
    assert checks.youden_margin(checks.roc_threshold(ids, oods),
                                ids, oods) == 0.0


@pytest.mark.parametrize("seed", range(4))
def test_a_threshold_swept_from_drifted_scores_reads_under_twice_the_drift(
        seed):
    ids, oods = scores(seed)
    rng = np.random.default_rng(100 + seed)
    span = max(ids.max(), oods.max()) - min(ids.min(), oods.min())
    d = 0.01 * span
    t = checks.roc_threshold(ids + rng.uniform(-d, d, ids.shape),
                             oods + rng.uniform(-d, d, oods.shape))
    assert checks.youden_margin(t, ids, oods) <= 2 * 0.01 + 1e-9


def test_a_threshold_off_the_optimum_reads_its_distance():
    ids = np.array([0.6, 0.7, 0.8, 0.9])
    oods = np.array([0.1, 0.2, 0.3, 0.4])
    # every threshold in [0.4, 0.6) separates the two sets (J = 1); 0.25
    # lets 0.3 and 0.4 in (J = 0.5). Moved by d, 0.25 reaches J = 0.75
    # once d > 0.05, and the best J falls to 0.75 once d reaches half the
    # 0.2 gap: d = 0.1 of the 0.8 range (less the sweep's step, 0.8/255)
    assert checks.youden_margin(0.5, ids, oods) == 0.0
    assert checks.youden_margin(0.25, ids, oods) == pytest.approx(
        0.1 / 0.8, abs=0.8 / 255 / 0.8)
    assert checks.youden_margin(float("nan"), ids, oods) == float("inf")


def test_the_roofline_reader_names_its_bound():
    read = harness.load_reader("head_select_roofline")
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    summary = trace.TraceSummary(
        chips=1, window_s=5.0, busy_s=5.0, kernel_s={"head_select": 4.0},
        kernel_calls={"head_select": 2}, collective_s=0.0,
        collective_exposed_s=0.0, device_ops=[], idle_gaps=[])
    ctx = SimpleNamespace(flops={"head_select": {"flops": 100.0,
                                                 "bytes": 5.0}},
                          trace=summary, peak=peak,
                          traced=SimpleNamespace(work={"rounds": 2}))
    got = read(ctx)
    assert got["bound"] == "flops"
    assert got["value"] == pytest.approx(100.0 * 2.0 / 4.0)
    assert got["bytes_s"] == pytest.approx(1.0)
