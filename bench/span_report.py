"""Where chip 0's idle time goes in one traced window of a label-round
cell, by the program's own spans.

    python3 bench/span_report.py --workload qwen3-1.7b.label_rounds --seed <n>

Sets the cell up as ``bench/run.py`` does, traces ``trace_units`` rounds
under the JAX profiler, and prints one JSON line: the harness's trace
reduction (``bench/trace.py``), chip 0's idle time split by innermost
``bench.*`` or ``idkd.*`` span (``bench/spans.py``) with the three
per-round numbers of :func:`bench.spans.round_phases`, the program's
compile-path counters over the traced rounds beside the harness's own
compile clock, and the device time of the operations whose stats name
each of the round's ``jax.named_scope``s. Runs no correctness check;
needs a TPU, like ``bench/run.py``. A program without the compile-path
counters (``repro.obs.compile_path``) or without the ``idkd.*`` spans
still gets the rest of the report.
"""
import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SCOPES = ("public_pass", "calibration_pass", "exchange")


def scope_ops(planes, lo_hi, scopes=SCOPES):
    """Per scope: the count and device seconds of chip 0's operations in
    the window whose string stats name the scope as a path component,
    and one such stat value; beside them, the stats of chip 0's longest
    operation in the window, to show what the stats hold."""
    from bench import trace as tr
    lo, hi = lo_hi
    dev = min((p for p in planes if tr.DEVICE_PLANE.match(p.name)
               and p.lines.get(tr.OPS_LINE)),
              key=lambda p: int(p.name.rsplit(":", 1)[1]))
    out = {s: {"ops": 0, "seconds": 0.0, "example": None} for s in scopes}
    longest = None
    for e in dev.lines[tr.OPS_LINE]:
        if e.end_ns <= lo or e.start_ns >= hi:
            continue
        if longest is None or e.dur_ns > longest.dur_ns:
            longest = e
        for s in scopes:
            hit = next((v for v in e.stats.values()
                        if s in v.replace("(", "/").split("/")), None)
            if hit is not None:
                out[s]["ops"] += 1
                out[s]["seconds"] += e.dur_ns / 1e9
                out[s]["example"] = out[s]["example"] or hit[:200]
    if longest is not None:
        out["longest_op"] = {"name": longest.name, "stats": {
            k: v[:200] for k, v in longest.stats.items()}}
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import harness, spans
    from bench import trace as tr
    try:
        from repro.obs.compile_path import KEYS
    except ImportError:
        KEYS = ()

    spec = harness.benchmark_spec()
    wl = harness.load_workload(args.workload)
    entry = next(w for w in spec["workloads"] if w["name"] == args.workload)
    wl.update(entry)
    try:
        devices = harness.require_chips(int(entry["chips"]))
    except harness.NoChip as e:
        print(f"span_report: {e}", file=sys.stderr)
        return 3
    harness.configure_jax()
    config, builder = harness.load_config(entry["config"])
    clock = harness.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    jax.monitoring.register_event_listener(clock.event)
    cell = builder.make_cell(config, wl, args.seed, devices)
    cell.setup()
    setup_s = time.perf_counter() - t_start

    round_stats = []
    unit = cell.unit

    def counted_unit():
        work = unit()
        stats = cell.fed.last_round_stats
        if KEYS and all(k in stats for k in KEYS):
            round_stats.append({k: stats[k] for k in KEYS})
        return work

    cell.unit = counted_unit
    out = harness.TRACE_DIR / f"{args.workload}.spans"
    win = harness.traced_window(cell, spec["run_seconds"], clock, out)
    planes = tr.read_xplane(tr.find_xplane(out))
    shutil.rmtree(out, ignore_errors=True)
    summary = tr.reduce_trace(planes, kernels=cell.kernels())
    split = spans.split_idle(planes)
    windows = [e for p in planes for line in p.lines.values() for e in line
               if e.name == tr.WINDOW_ANNOTATION]
    lo_hi = (min(e.start_ns for e in windows), max(e.end_ns for e in windows))
    program = {k: sum(st[k] for st in round_stats) for k in KEYS
               if round_stats}
    idkd_idle = sum(v for k, v in split.span_idle_s.items()
                    if k.startswith("idkd."))
    result = {
        "workload": args.workload, "seed": args.seed, "setup_s": setup_s,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind},
        "window": {"seconds": win.seconds, "units": win.units,
                   "seconds_per_round": win.seconds / win.units,
                   "compile_s": win.compile_s, "compiles": win.compiles,
                   "cache_hits": win.cache_hits},
        "program_compile_path": program,
        "rounds": round_stats,
        "trace": {"window_s": summary.window_s, "busy_s": summary.busy_s,
                  "idle_share": summary.idle_share,
                  "idle_gaps_bench": summary.idle_gaps},
        "idle_s": split.idle_s,
        "span_idle_sum_s": sum(split.span_idle_s.values()),
        "idkd_idle_share": idkd_idle / split.idle_s if split.idle_s else None,
        "span_idle_s": split.span_idle_s,
        "span_s": split.span_s,
        "idle_gaps": split.idle_gaps,
        "metrics": spans.round_phases(split, win.units, round_stats),
        "scopes": scope_ops(planes, lo_hi),
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
