"""Benchmark orchestrator — one section per paper table.

Prints ``name,us_per_call,derived`` CSV rows (harness contract) and the
rendered markdown tables. Results are cached under experiments/bench/, so
re-runs are incremental.

    PYTHONPATH=src python -m benchmarks.run            # all sections
    PYTHONPATH=src python -m benchmarks.run --only table2,roofline
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

sys.path.insert(0, "src")

from benchmarks import (bench_driver, bench_kernels,  # noqa: E402
                        bench_schedule, fig3_homogenize, roofline,
                        table2_noniid, table3_topology, table4_public,
                        table6_comm, table7_scale)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SECTIONS = {
    "table2": lambda: table2_noniid.run(),
    "table3": lambda: table3_topology.run(),
    "table4": lambda: table4_public.run(),
    "table6": lambda: table6_comm.run(),
    "table7": lambda: table7_scale.run(),
    "fig3": lambda: fig3_homogenize.run()[:2],
    "kernels": lambda: bench_kernels.run(),
    "labeling": lambda: bench_kernels.bench_labeling(),
    "driver": lambda: bench_driver.run(),
    "schedule": lambda: bench_schedule.run(),
    "roofline": lambda: roofline.run(),
}

RENDERERS = {
    "table2": table2_noniid.render,
    "table3": table3_topology.render,
    "table4": table4_public.render,
    "table6": table6_comm.render,
    "table7": table7_scale.render,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    args = ap.parse_args()
    enable_compile_cache()
    names = (args.only.split(",") if args.only else list(SECTIONS))
    print("name,us_per_call,derived")
    failures = []
    for name in names:
        t0 = time.time()
        try:
            rows, csv = SECTIONS[name]()
        except Exception:  # noqa: BLE001 — keep the report going
            traceback.print_exc()
            failures.append(name)
            continue
        for row in csv:
            print(",".join(str(x) for x in row), flush=True)
        if name in RENDERERS and rows:
            print(f"\n## {name}\n{RENDERERS[name](rows)}\n", flush=True)
        elif rows:
            print(f"\n## {name}\n{rows}\n", flush=True)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failures:
        raise SystemExit(f"benchmark sections failed: {failures}")


if __name__ == "__main__":
    main()
