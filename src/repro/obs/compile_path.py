"""Per-round compile-path counters from ``jax.monitoring``.

One process-wide listener, registered on first use, sums the seconds
and counts of JAX's compile-path events: tracing a function to a jaxpr,
lowering the jaxpr to an MLIR module, the backend compile (JAX reports a
program read back from the persistent compilation cache as a backend
compile too) and persistent-cache hits. A :class:`CompileWatch` takes
the difference over a block of code; the label-round hooks put it into
``last_round_stats``, which the scheduler forwards to the ``labels``
run-log event, so a run log shows which round traced, lowered or
compiled again.

The totals only grow; readers take differences, so nothing here is
reset between callers.
"""
from __future__ import annotations

import threading
from typing import Dict

# monitoring event -> the seconds key it feeds
DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_compile_s",
}
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
KEYS = ("trace_s", "lower_s", "backend_compile_s", "compiles",
        "cache_hits", "compile_path_s")

_lock = threading.Lock()
_totals: Dict[str, float] = {"trace_s": 0.0, "lower_s": 0.0,
                             "backend_compile_s": 0.0, "compiles": 0,
                             "cache_hits": 0}
_registered = False


def _on_duration(event: str, duration: float, **_) -> None:
    key = DURATION_EVENTS.get(event)
    if key is None:
        return
    with _lock:
        _totals[key] += duration
        if event == BACKEND_COMPILE_EVENT:
            _totals["compiles"] += 1


def _on_event(event: str, **_) -> None:
    if event == CACHE_HIT_EVENT:
        with _lock:
            _totals["cache_hits"] += 1


def totals() -> Dict[str, float]:
    """The process's compile-path totals so far (registers the listener
    on the first call; events before it are not counted)."""
    global _registered
    with _lock:
        if not _registered:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            jax.monitoring.register_event_listener(_on_event)
            _registered = True
        return dict(_totals)


class CompileWatch:
    """``with CompileWatch() as w: ...`` then ``w.stats`` holds the
    block's :data:`KEYS`."""

    def __init__(self):
        self.stats: Dict[str, float] = {}
        self._before: Dict[str, float] = {}

    def __enter__(self) -> "CompileWatch":
        self._before = totals()
        return self

    def __exit__(self, *exc) -> None:
        after = totals()
        self.stats = {k: after[k] - self._before[k] for k in after}
        self.stats["compile_path_s"] = (self.stats["trace_s"]
                                        + self.stats["lower_s"]
                                        + self.stats["backend_compile_s"])
