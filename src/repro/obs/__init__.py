"""Federation telemetry: metrics bus + structured events + trace spans.

Three layers, all off by default (a run with ``telemetry=None`` executes
byte-for-byte the code it always did):

  1. **metrics bus** (:mod:`repro.obs.metrics`) — a pytree carried
     through the jitted runners, accumulating per-node loss / grad norm /
     consensus distance / EF residual with zero host syncs;
  2. **run events** (:mod:`repro.obs.runlog`) — schema-checked JSONL
     (``run.jsonl``) of segments, churn, label rounds, ledger traffic,
     metric flushes, evals;
  3. **trace spans** (:mod:`repro.obs.trace`) — one helper,
     :func:`repro.obs.trace.span`, around scheduler phases and the label
     round's phases: always a ``jax.profiler.TraceAnnotation`` (so the
     spans land in a device trace, on its clock), and a Chrome
     trace_event span (``trace.json``, Perfetto-loadable, same wall
     clock) in the current recorder, with an optional ``jax.profiler``
     hand-off.

Beside them, :mod:`repro.obs.compile_path` counts the compile path
(trace, lower, backend compile, persistent-cache hits) per label round
from ``jax.monitoring``; the hooks add the round's counts to
``last_round_stats``, hence to the ``labels`` event.

:class:`Telemetry` is the facade the simulator / launch driver / tests
hold; the scheduler only ever calls ``event`` / ``span`` /
``flush_metrics`` on it.
"""
from __future__ import annotations

from contextlib import ExitStack
from pathlib import Path
from typing import Optional

from repro.obs import log  # noqa: F401 (re-export)
from repro.obs.runlog import (EVENT_SCHEMA, RunLog, read_events,
                              validate_runlog)
from repro.obs.trace import (TraceRecorder, recording, span,
                             start_jax_profiler, stop_jax_profiler,
                             validate_trace)

RUNLOG_NAME = "run.jsonl"
TRACE_NAME = "trace.json"


class Telemetry:
    """One run's telemetry sinks + the metrics-bus enable flag.

    ``out_dir=None`` keeps everything in memory (metrics bus only —
    useful for overhead benches); otherwise ``run.jsonl`` streams as the
    run progresses and ``trace.json`` is written by :meth:`close`. With
    ``trace=True`` the recorder is current (:func:`repro.obs.trace.
    recording`) from here until :meth:`close`, so the program's own
    :func:`~repro.obs.trace.span` calls land in ``trace.json`` too.
    """

    def __init__(self, out_dir=None, *, metrics: bool = True,
                 events: bool = True, trace: bool = False,
                 jax_profile: bool = False, meta: Optional[dict] = None):
        self.out_dir = Path(out_dir) if out_dir is not None else None
        self.metrics_enabled = bool(metrics)
        self.runlog: Optional[RunLog] = None
        self.tracer: Optional[TraceRecorder] = None
        self._profiling = False
        self._current = ExitStack()
        if self.out_dir is not None and events:
            self.runlog = RunLog(self.out_dir / RUNLOG_NAME)
        if trace:
            self.tracer = TraceRecorder()
        if meta:
            self.event("run_meta", **meta)
        if jax_profile and self.out_dir is not None:
            start_jax_profiler(self.out_dir / "jax_profile")
            self._profiling = True
        if self.tracer is not None:
            self._current.enter_context(recording(self.tracer))

    # -- sinks ---------------------------------------------------------------
    def event(self, ev: str, **fields) -> None:
        if self.runlog is not None:
            self.runlog.emit(ev, **fields)

    def span(self, name: str, cat: str = "sched", **args):
        return span(name, cat, **args)

    def flush_metrics(self, step: int, metrics, **extra) -> None:
        """device_get + summarize the metrics pytree into one event."""
        if metrics is None:
            return
        from repro.obs import metrics as obs_metrics
        summary = obs_metrics.summarize(metrics)
        self.event("metrics", step=step, **summary, **extra)

    def close(self) -> None:
        self._current.close()
        if self._profiling:
            stop_jax_profiler()
            self._profiling = False
        if self.tracer is not None and self.out_dir is not None:
            self.tracer.export(self.out_dir / TRACE_NAME)
        if self.runlog is not None:
            self.runlog.close()

    def __enter__(self) -> "Telemetry":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


__all__ = ["Telemetry", "RunLog", "TraceRecorder", "EVENT_SCHEMA",
           "RUNLOG_NAME", "TRACE_NAME", "log", "read_events",
           "validate_runlog", "validate_trace", "start_jax_profiler",
           "stop_jax_profiler"]
