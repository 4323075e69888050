"""Peaks table and the FLOP counts from shapes, against hand counts."""
import json

import pytest

from bench import harness, peaks


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v99")


def test_roofline_names_its_bound():
    p = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert peaks.roofline_seconds(1000.0, 10.0, p) == (10.0, "flops")
    assert peaks.roofline_seconds(10.0, 1000.0, p) == (100.0, "bytes")


def test_qwen3_three_layers_is_six_n_t():
    cfg, builder = harness.load_config("qwen3-1.7b")
    assert cfg["num_hidden_layers"] == 28
    cfg = {**cfg, "num_hidden_layers": 3}
    # by hand: per layer q 2048x2048, k and v 2048x1024, o 2048x2048,
    # SwiGLU 3 x 2048x6144; the tied head 2048 x 151,936
    layer = 2048 * 2048 * 2 + 2048 * 1024 * 2 + 3 * 2048 * 6144
    n = 3 * layer + 2048 * 151936
    assert n == 462_159_872
    S, T = 128, 1536
    attn = 3 * 2 * 2 * 16 * 128 * (S + 1) / 2
    fwd = builder.forward_flops_per_token(cfg, S)
    assert fwd - attn == 2 * n
    assert 3 * (fwd - attn) * T == 6 * n * T


def test_benchmark_names_every_file():
    spec = harness.benchmark_spec()
    assert {c["name"] for c in spec["configs"]} <= set(harness.list_configs())
    assert {w["name"] for w in spec["workloads"]} <= set(
        harness.list_workloads())
    names = {m["name"] for m in spec["end_to_end"] + spec["per_layer"]}
    assert names <= set(harness.list_metrics())
    for c in spec["configs"]:
        json.loads((harness.ROOT / c["file"]).read_text())
