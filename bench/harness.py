"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The harness is driven by data. ``BENCHMARK.json`` names the cells and the
metrics; everything that belongs to one of them sits in a file of its own
that the harness finds by name:

* ``bench/workloads/<workload>.json``: the traffic (sizes, lengths,
  segment and round counts) and the limits of the correctness check;
* ``bench/configs/<config>.json``: the model configuration as it is run,
  and ``bench/configs/<config>.py``: the cell builder for that model (it
  makes weights and data from the seed, drives the program, counts the
  work's FLOPs and bytes from shapes) and its plain reference;
* ``bench/metrics/<metric>.py``: a reader with ``read(ctx)`` that returns
  the metric's value, or None where the run gives it nothing to read, or
  a dict with the value under ``"value"`` and notes beside it (which
  term of a roofline bounds it).

A run sets up (weights and data made on the device from the seed, every
shape the window uses compiled, the check's first steps driven), then
calls the cell's unit of work (a scheduler segment or a homogenization
round, each ending on a device sync) until ``--seconds`` have passed,
then runs the plain reference and prints one JSON line. With
``--trace 1`` the window runs under the JAX profiler and the line holds
the per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
# fixed paths inside the checkout: the compile cache's path is part of
# its key, and nothing is written outside the checkout
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


# ------------------------------------------------------------- discovery
def load_module(path: Path, name: Optional[str] = None):
    """Import a file whose name need not be a Python identifier
    (``qwen3-1.7b.py``, ``device_idle_share.round.py``)."""
    name = name or "bench_" + "".join(
        c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def list_workloads(base: Path = BENCH) -> List[str]:
    return sorted(p.stem for p in (base / "workloads").glob("*.json"))


def list_metrics(base: Path = BENCH) -> List[str]:
    return sorted(p.stem for p in (base / "metrics").glob("*.py")
                  if not p.name.startswith("_"))


def list_configs(base: Path = BENCH) -> List[str]:
    return sorted(p.stem for p in (base / "configs").glob("*.json"))


def benchmark_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_workload(name: str, base: Path = BENCH) -> dict:
    path = base / "workloads" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no workload file {path.relative_to(base.parent)}; "
                       f"known: {list_workloads(base)}")
    wl = json.loads(path.read_text())
    wl["name"] = name
    return wl


def load_config(name: str, base: Path = BENCH):
    """(configuration dict, builder module) of one model configuration."""
    cfg = json.loads((base / "configs" / f"{name}.json").read_text())
    return cfg, load_module(base / "configs" / f"{name}.py")


def metrics_for(spec: dict, workload: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that apply to a cell:
    those without a ``workloads`` key, and those that list it."""
    return [m for m in spec[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(metric: str, base: Path = BENCH) -> Callable:
    return load_module(base / "metrics" / f"{metric}.py").read


# ---------------------------------------------------------------- clocks
class CompileClock:
    """Sums XLA backend compile time reported by ``jax.monitoring``, and
    counts the compiles that the persistent cache served (JAX reports
    those as compiles too)."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.hits = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1

    def event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    def mark(self):
        return self.seconds, self.count, self.hits

    def since(self, mark):
        return (self.seconds - mark[0], self.count - mark[1],
                self.hits - mark[2])


@dataclass
class Window:
    """What one measured (or traced) window did."""
    seconds: float = 0.0
    units: int = 0
    work: Dict[str, float] = field(default_factory=dict)
    compiles: int = 0
    cache_hits: int = 0
    compile_s: float = 0.0

    def add(self, work: Dict[str, float]) -> None:
        self.units += 1
        for k, v in work.items():
            self.work[k] = self.work.get(k, 0) + v


@dataclass
class Context:
    """What a metric reader may read."""
    setup_s: float
    window: Window
    chips: int
    peak: dict
    flops: Dict[str, Any]
    trace: Any = None                   # trace.TraceSummary, traced runs
    traced: Optional[Window] = None     # the traced window's work


# ----------------------------------------------------------------- device
def configure_jax() -> None:
    """Compile cache inside the checkout. Runs before the first compile
    (looking up the devices compiles nothing)."""
    import jax
    # JAX writes its entries into the directory but does not create it
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found: jax.devices()[0].platform is "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, found "
                     f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------- window
def run_window(cell, seconds: float, clock: CompileClock,
               max_units: Optional[int] = None) -> Window:
    """Call the cell's unit of work until ``seconds`` have passed (or
    ``max_units`` units ran); each unit ends on a device sync."""
    import jax
    win = Window()
    mark = clock.mark()
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            win.add(cell.unit())
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds or (max_units and win.units >= max_units):
                break
    win.seconds = time.perf_counter() - t0
    win.compile_s, win.compiles, win.cache_hits = clock.since(mark)
    return win


def traced_window(cell, seconds: float, clock: CompileClock, out: Path):
    import jax
    shutil.rmtree(out, ignore_errors=True)
    # no Python function tracer: it would record every call of the
    # program's host code and slow the host the trace is meant to see
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        win = run_window(cell, seconds, clock,
                         max_units=cell.workload.get("trace_units"))
    finally:
        jax.profiler.stop_trace()
    return win


# ---------------------------------------------------------------- output
def _metric_values(entries: List[dict], ctx: Context) -> Dict[str, dict]:
    out = {}
    for m in entries:
        value = load_reader(m["name"])(ctx)
        if value is None:
            continue
        notes = dict(value) if isinstance(value, dict) else {"value": value}
        out[m["name"]] = {"value": float(notes.pop("value")),
                          "unit": m["unit"], **notes}
    return out


def check_lines(checks: List[dict]) -> List[str]:
    return [f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})"
            for c in checks]


def verdict(checks: List[dict]) -> bool:
    return bool(checks) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, t_start: float, *, require_tpu: bool = True,
        prepare: Optional[Callable] = None,
        config_update: Optional[dict] = None,
        workload_update: Optional[dict] = None) -> dict:
    """One run; returns the result object.

    For the tests only: ``require_tpu=False`` skips the look for a chip
    and drives the rest of a run on the CPU, ``config_update`` and
    ``workload_update`` shrink the cell to a size the CPU holds, and
    ``prepare(cell)`` is handed the cell before set-up."""
    spec = benchmark_spec()
    wl = load_workload(args.workload)
    entry = next((w for w in spec["workloads"]
                  if w["name"] == args.workload), None)
    if entry is None:
        raise KeyError(f"workload {args.workload!r} is not in "
                       "BENCHMARK.json")
    wl.update(entry)
    wl.update(workload_update or {})
    import jax
    if require_tpu:
        devices = require_chips(int(entry["chips"]))
        configure_jax()
    else:
        devices = jax.devices()[:int(entry["chips"])]
    from bench.peaks import peaks
    # a CPU run reports no device metric: its nominal peak only lets
    # the readers run in the tests
    peak = peaks(devices[0].device_kind) if require_tpu else \
        {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    config, builder = load_config(entry["config"])
    config = {**config, **(config_update or {})}
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    jax.monitoring.register_event_listener(clock.event)
    try:
        return _measure(args, t_start, spec, wl, config, builder, devices,
                        peak, clock, prepare)
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
        jax.monitoring.unregister_event_listener(clock.event)


def _measure(args, t_start, spec, wl, config, builder, devices, peak,
             clock, prepare) -> dict:
    cell = builder.make_cell(config, wl, args.seed, devices)
    if prepare is not None:
        prepare(cell)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    setup_compile_s, setup_compiles, setup_hits = clock.mark()

    summary = None
    if args.trace:
        out = TRACE_DIR / args.workload
        win = traced_window(cell, args.seconds, clock, out)
    else:
        win = run_window(cell, args.seconds, clock)
    mem = memory_peak(devices)
    flops = cell.flops()
    if args.trace:
        from bench import trace as tr
        summary = tr.summarize_file(tr.find_xplane(out),
                                    kernels=cell.kernels())
        shutil.rmtree(out, ignore_errors=True)

    cell.release()
    checks = cell.check()
    correct = verdict(checks)

    ctx = Context(setup_s=setup_s, window=win,
                  chips=len(devices), peak=peak, flops=flops,
                  trace=summary, traced=win if args.trace else None)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = _metric_values(metrics_for(spec, args.workload, kind), ctx)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": win.units,
              # a unit that fails raises and ends the run
              "failed": 0, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in summary.device_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
    result["window"] = {"seconds": win.seconds, "units": win.units,
                        "work": win.work, "compiles": win.compiles,
                        "cache_hits": win.cache_hits,
                        "compile_s": win.compile_s,
                        "setup_compiles": setup_compiles,
                        "setup_cache_hits": setup_hits,
                        "setup_compile_s": setup_compile_s}
    result["checks"] = {c["name"]: {"value": c["value"],
                                    "limit": c["limit"]} for c in checks}
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    # the TPU runtime logs to /tmp/tpu_logs unless told otherwise; a run
    # writes nothing outside its checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    args = parse_args(argv)
    for p in (str(ROOT), str(SRC)):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        result = run(args, t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    checks = [{"name": k, **v} for k, v in result["checks"].items()]
    for line in check_lines(checks):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
