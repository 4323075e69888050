"""Readings that set a cell's correctness limits, at the cell's own size
on the chip, in one process:

* the program's compared numbers on every seed of ``--seeds`` (their
  largest is each number's lower reading);
* the control's on every seed of ``--control-seeds``: the plain
  reference put in the program's place at the next lower precision
  (fp8 for a bfloat16 configuration), compared with the float32
  reference the same way.

    python3 bench/control.py --workload <name> --seeds 1-12 \
        --control-seeds 1-3 [--out FILE]

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def seed_range(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    spec = harness.benchmark_spec()
    entry = next(w for w in spec["workloads"] if w["name"] == args.workload)
    wl = harness.load_workload(args.workload)
    wl.update(entry)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devices = harness.require_chips(int(entry["chips"]))
    harness.configure_jax()
    config, builder = harness.load_config(entry["config"])
    controls = set(seed_range(args.control_seeds))
    rows = []
    for seed in seed_range(args.seeds):
        t0 = time.perf_counter()
        cell = builder.make_cell(config, wl, seed, devices)
        cell.setup()
        cell.unit()
        cell.release()
        nums = cell.numbers(with_control=seed in controls)
        row = {"seed": seed, "seconds": time.perf_counter() - t0, **nums}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for name in rows[0]["program"]:
        prog = [r["program"][name] for r in rows]
        entry = {"lower": max(prog), "program": prog}
        for kind in sorted({k for r in rows for k in r} - {"seed",
                                                           "seconds",
                                                           "program"}):
            got = [r[kind][name] for r in rows if kind in r]
            entry[kind] = got
            entry[f"{kind}_min"] = min(got)
        summary[name] = entry
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"rows": rows,
                                              "summary": summary},
                                             indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
