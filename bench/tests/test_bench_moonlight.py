"""The moonlight-16b-a3b label-round cell at a size the CPU holds, the
program in float32 on the configuration's bfloat16 weights: a sound run
is correct, the control (the reference at fp8 in the program's place)
is not, and a planted routing fault makes ``correct`` come out false.
Also the router fit: every expert's share of the fitting batch's picks
ends within its tolerance."""
import time

import numpy as np

from bench import harness

W = "moonlight-16b-a3b.label_rounds"
TINY_CONFIG = {
    "num_hidden_layers": 4, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 256,
    "moe_intermediate_size": 64, "n_routed_experts": 4,
    "num_experts_per_tok": 4, "vocab_size": 256, "torch_dtype": "float32",
    "deployment": {"published_n_routed_experts": 16, "held_experts": [4, 4],
                   "chips_per_layer": 4}}
TINY_WORKLOAD = {"seq_len": 16, "public_seqs": 8, "calibration_seqs": 4,
                 "fit_seqs": 16, "stream_microbatch": 4,
                 "reference_seqs": 4, "reference_rows": 64}


def run_tiny(seed: int = 2**31 + 11):
    cells = []
    args = harness.parse_args(["--workload", W, "--seed", str(seed),
                               "--seconds", "0.01"])
    result = harness.run(args, time.perf_counter(), require_tpu=False,
                         prepare=cells.append, config_update=TINY_CONFIG,
                         workload_update=TINY_WORKLOAD)
    return result, cells[0]


def failed(numbers: dict) -> bool:
    lim = harness.load_workload(W)["limits"]
    return any(numbers[k] > lim[k] for k in lim)


def test_sound_run_is_correct_the_control_is_not_and_the_fit_balances():
    result, cell = run_tiny()
    assert result["correct"], result["checks"]
    nums = cell.numbers(with_control=True)
    assert not failed(nums["program"])
    assert failed(nums["control"]), nums["control"]
    fit = [layer for node in cell.fit_report for layer in node]
    assert len(fit) == 2 * 3
    tol = harness.load_module(
        harness.BENCH / "configs" / "moonlight-16b-a3b.py").FIT_TOLERANCE
    assert max(layer["deviation"] for layer in fit) <= tol
    # 4 of 16 experts held: a quarter of the picks once balanced
    assert np.allclose([layer["fitted"] for layer in fit], 0.25, atol=0.05)
    assert cell.counts["held_assignments"] > 0


def test_routing_without_the_correction_bias(monkeypatch):
    """The router ranks by the bare scores, leaving out the fitted
    correction bias: other experts are picked."""
    from repro.models import moe
    route = moe._route

    def unbiased(params, x, cfg):
        return route(dict(params, router_bias=0 * params["router_bias"]),
                     x, cfg)

    monkeypatch.setattr(moe, "_route", unbiased)
    assert not run_tiny()[0]["correct"]
