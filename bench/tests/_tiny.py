"""Shared by the fault tests: drive the rest of a benchmark run on the
CPU at a size a test run holds, with the look for a chip skipped."""
import time

from bench import harness

TINY = {
    "qwen3-1.7b.label_rounds": (
        {"num_hidden_layers": 1, "hidden_size": 2048,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 128, "intermediate_size": 256, "vocab_size": 1024},
        {"seq_len": 16, "public_seqs": 8, "calibration_seqs": 4,
         "reference_rows": 64}),
}


def run_tiny(workload: str, seed: int = 2**31 + 11, limits=None):
    """One run of ``workload`` at its tiny size; returns (result, cell).
    ``limits`` replaces the workload file's."""
    cfg, wl = TINY[workload]
    if limits is not None:
        wl = {**wl, "limits": limits}
    cells = []
    args = harness.parse_args(["--workload", workload, "--seed", str(seed),
                               "--seconds", "0.01"])
    result = harness.run(args, time.perf_counter(), require_tpu=False,
                         prepare=cells.append, config_update=cfg,
                         workload_update=wl)
    return result, cells[0]


def limits(workload: str) -> dict:
    return harness.load_workload(workload)["limits"]
