"""Trace spans on the profiler's clock, and Chrome ``trace_event`` JSON.

One helper, :func:`span`, marks a phase of the program. It always opens a
``jax.profiler.TraceAnnotation`` (about a microsecond when no profiler
session is active), so inside a ``jax.profiler`` trace the phase lands on
the host plane of the ``.xplane.pb``, on the same clock as the device's
operations: a device trace can then say what the host was doing while
the chip sat idle. If a :class:`TraceRecorder` is *current* (see
:func:`recording`; ``Telemetry(trace=True)`` makes its recorder current
until ``close()``), the helper also records the span there, with the
enclosing span's name as its ``parent`` arg.

A :class:`TraceRecorder` collects complete ("ph": "X") spans and exports
the standard ``{"traceEvents": [...]}`` document that chrome://tracing
and Perfetto (https://ui.perfetto.dev) load directly. Its timestamps are
``time.time_ns()`` in microseconds since the epoch: the wall clock the
profiler stamps its host events with (an xplane event starts at the
``profile_start_time`` stat of the ``Task Environment`` plane plus its
own offset), so a ``trace.json`` span and its xplane event start at the
same instant.

Span args are plain ints and strings: a span never holds a device array
and never waits for the device.

For device-level detail, :func:`start_jax_profiler` hands off to
``jax.profiler`` (TensorBoard/Perfetto-compatible output). A run that
asks for a device trace and cannot start or stop one fails loudly.
"""
from __future__ import annotations

import json
import os
import time
from contextlib import contextmanager
from contextvars import ContextVar
from pathlib import Path
from typing import Any, Dict, List, Optional

_CURRENT: ContextVar[Optional["TraceRecorder"]] = ContextVar(
    "repro_obs_trace_recorder", default=None)


class TraceRecorder:
    """In-memory span recorder exporting Chrome trace_event JSON, stamped
    with the wall clock (µs since the epoch)."""

    def __init__(self, pid: int = 0):
        self.pid = pid if pid else os.getpid()
        self.events: List[Dict[str, Any]] = []
        self._open: List[str] = []

    @contextmanager
    def span(self, name: str, cat: str = "sched", **args):
        """Record one complete span; a span opened inside another gets
        the enclosing span's name as its ``parent`` arg."""
        if self._open:
            args = {"parent": self._open[-1], **args}
        self._open.append(name)
        start = time.time_ns()
        try:
            yield self
        finally:
            end = time.time_ns()
            self._open.pop()
            self.events.append({
                "name": name, "cat": cat, "ph": "X",
                "ts": round(start / 1e3, 3),
                "dur": round((end - start) / 1e3, 3),
                "pid": self.pid, "tid": 0,
                "args": {k: _arg(v) for k, v in args.items()},
            })

    def export(self, path) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        doc = {"traceEvents": self.events, "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


def current_recorder() -> Optional[TraceRecorder]:
    """The recorder :func:`span` records into, or None."""
    return _CURRENT.get()


@contextmanager
def recording(recorder: Optional[TraceRecorder]):
    """Make ``recorder`` current for the block (None: record nothing)."""
    token = _CURRENT.set(recorder)
    try:
        yield recorder
    finally:
        _CURRENT.reset(token)


@contextmanager
def span(name: str, cat: Optional[str] = None, **args):
    """A ``jax.profiler.TraceAnnotation`` named ``name`` with ``args`` as
    its stats, recorded in the current :class:`TraceRecorder` too. The
    Chrome category defaults to the name's prefix (``idkd`` for
    ``idkd.round``). ``args`` are plain ints and strings."""
    import jax
    rec = _CURRENT.get()
    with jax.profiler.TraceAnnotation(name, **args):
        if rec is None:
            yield
        else:
            with rec.span(name, cat or name.partition(".")[0], **args):
                yield


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
