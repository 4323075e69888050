"""Trace spans: Chrome ``trace_event`` JSON around scheduler phases.

A :class:`TraceRecorder` collects complete ("ph": "X") spans with
microsecond wall-clock timestamps and exports the standard
``{"traceEvents": [...]}`` document that chrome://tracing and Perfetto
(https://ui.perfetto.dev) load directly. Spans wrap scheduler segments,
label rounds, evals, and comm/compile boundaries; the first invocation
of a freshly built runner is tagged ``compile=True`` so XLA compilation
cost is visible as a distinct slice.

For device-level detail, :func:`start_jax_profiler` hands off to
``jax.profiler`` (TensorBoard/Perfetto-compatible output). A run that
asks for a device trace and cannot start or stop one fails loudly.
"""
from __future__ import annotations

import json
import os
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Any, Dict, List


class TraceRecorder:
    """In-memory span recorder exporting Chrome trace_event JSON."""

    def __init__(self, pid: int = 0):
        self.pid = pid if pid else os.getpid()
        self.events: List[Dict[str, Any]] = []
        self._t0 = time.perf_counter_ns()

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    @contextmanager
    def span(self, name: str, cat: str = "sched", **args):
        start = self._now_us()
        try:
            yield self
        finally:
            end = self._now_us()
            self.events.append({
                "name": name, "cat": cat, "ph": "X",
                "ts": round(start, 3), "dur": round(end - start, 3),
                "pid": self.pid, "tid": 0,
                "args": {k: _arg(v) for k, v in args.items()},
            })

    def instant(self, name: str, cat: str = "sched", **args) -> None:
        self.events.append({
            "name": name, "cat": cat, "ph": "i", "s": "g",
            "ts": round(self._now_us(), 3), "pid": self.pid, "tid": 0,
            "args": {k: _arg(v) for k, v in args.items()},
        })

    def export(self, path) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        doc = {"traceEvents": self.events, "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


def _arg(v: Any) -> Any:
    if hasattr(v, "tolist"):
        return v.tolist()
    if isinstance(v, (int, float, bool, str)) or v is None:
        return v
    return str(v)


def validate_trace(path) -> int:
    """Check a trace JSON is Perfetto-loadable; returns the event count.

    Loadable here means: a JSON object with a ``traceEvents`` list whose
    entries each carry ``name``/``ph``/``ts`` (and ``dur`` for complete
    events) — the minimum the trace_event spec requires.
    """
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError(f"{path}: no traceEvents list")
    for i, ev in enumerate(events):
        for k in ("name", "ph", "ts", "pid"):
            if k not in ev:
                raise ValueError(f"{path}: traceEvents[{i}] missing {k!r}")
        if ev["ph"] == "X" and "dur" not in ev:
            raise ValueError(f"{path}: traceEvents[{i}] complete event "
                             f"without dur")
    if not events:
        raise ValueError(f"{path}: empty trace")
    return len(events)


def start_jax_profiler(log_dir) -> None:
    """``jax.profiler.start_trace`` hand-off (device detail); a profiler
    failure raises."""
    import jax
    jax.profiler.start_trace(str(log_dir))


def stop_jax_profiler() -> None:
    import jax
    jax.profiler.stop_trace()
