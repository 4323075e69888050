"""Chip 0's idle time put down to the program's phases.

The program opens ``jax.profiler.TraceAnnotation`` spans named ``idkd.*``
around the host phases of a label round (``repro.obs.trace.span``); the
harness opens its own ``bench.*`` spans around the calls it makes. Both
sit on the host plane of the trace, on the device's clock. This
reduction splits every idle interval of chip 0 inside the traced window
at the spans' boundaries and puts each piece down to the innermost span
open over it (``bench.window`` excluded, as in ``bench/trace.py``), so
the pieces sum to chip 0's idle time in the window.

:func:`round_phases` folds the split into three numbers per traced round
(seconds of chip 0 idle time):

* ``round_idle_s.dispatch``: under ``idkd.public_pass`` or
  ``idkd.calibration_pass``, where the host traces, lowers and dispatches
  the two passes while the chip waits;
* ``round_idle_s.host``: under the round's other host phases (``idkd.round``
  itself, ``idkd.inputs``, ``idkd.threshold``, ``idkd.exchange``,
  ``idkd.readback``, ``idkd.topk_overlap``), with ``unattributed_s``
  beside it: idle time under the harness's spans alone;
* ``round_compile_path_s``: the program's own compile-path seconds
  (``last_round_stats["compile_path_s"]``, from ``jax.monitoring``) over
  the traced rounds.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass
from typing import Dict, List, Mapping, Sequence, Tuple

from bench import trace as tr

PREFIXES = ("bench.", "idkd.")
NO_SPAN = "outside any harness span"
DISPATCH = ("idkd.public_pass", "idkd.calibration_pass")
HOST = ("idkd.round", "idkd.inputs", "idkd.threshold", "idkd.exchange",
        "idkd.readback", "idkd.topk_overlap")


@dataclass
class SpanSplit:
    idle_s: float                       # chip 0's idle seconds in the window
    span_idle_s: Dict[str, float]       # those seconds by innermost span
    span_s: Dict[str, float]            # host seconds by span, in the window
    idle_gaps: List[Tuple[str, float]]  # the longest gaps, innermost span


def _segments(spans: Sequence[tr.Event], lo: float, hi: float):
    """The window cut at every span boundary: (starts, labels), where
    ``labels[i]`` is the innermost (shortest) span open over
    ``[starts[i], starts[i + 1])``."""
    cuts = sorted({lo, hi} | {t for s in spans for t in (s.start_ns, s.end_ns)
                              if lo < t < hi})
    starts, labels = [], []
    for a in cuts[:-1]:
        open_ = [s for s in spans if s.start_ns <= a < s.end_ns]
        starts.append(a)
        labels.append(min(open_, key=lambda s: s.dur_ns).name if open_
                      else NO_SPAN)
    return starts, labels, cuts[-1]


def split_idle(planes: Sequence[tr.Plane], top: int = tr.TOP) -> SpanSplit:
    """Split chip 0's idle time in the traced window by innermost span."""
    spans = [s for p in PREFIXES for s in tr._annotations(planes, p)]
    windows = [s for s in spans if s.name == tr.WINDOW_ANNOTATION]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    lo = min(s.start_ns for s in windows)
    hi = max(s.end_ns for s in windows)
    spans = [s for s in spans if s.name != tr.WINDOW_ANNOTATION]
    devices = sorted((p for p in planes if tr.DEVICE_PLANE.match(p.name)
                      and p.lines.get(tr.OPS_LINE)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if not devices:
        raise ValueError("the trace holds no device plane with operations")
    busy = tr._merge(iv for e in devices[0].lines[tr.OPS_LINE]
                     for iv in tr._clip([(e.start_ns, e.end_ns)], lo, hi))
    idle = tr._subtract([(lo, hi)], busy)

    starts, labels, end = _segments(spans, lo, hi)
    span_idle: Dict[str, float] = {}
    for a, b in idle:
        i = bisect.bisect_right(starts, a) - 1
        while i < len(starts) and starts[i] < b:
            seg_end = starts[i + 1] if i + 1 < len(starts) else end
            piece = min(b, seg_end) - max(a, starts[i])
            if piece > 0:
                span_idle[labels[i]] = span_idle.get(labels[i], 0.0) + piece
            i += 1
    span_s: Dict[str, float] = {}
    for s in spans:
        t = tr._length(tr._clip([(s.start_ns, s.end_ns)], lo, hi))
        if t:
            span_s[s.name] = span_s.get(s.name, 0.0) + t
    gaps = [(tr._label(spans, (a + b) / 2), (b - a) / 1e9) for a, b in idle]
    return SpanSplit(
        idle_s=tr._length(idle) / 1e9,
        span_idle_s={k: v / 1e9 for k, v in span_idle.items()},
        span_s={k: v / 1e9 for k, v in span_s.items()},
        idle_gaps=sorted(gaps, key=lambda g: -g[1])[:top])


def round_phases(split: SpanSplit, rounds: int,
                 round_stats: Sequence[Mapping[str, float]] = ()
                 ) -> Dict[str, dict]:
    """The three per-round numbers, each with its notes; ``round_stats``
    are the traced rounds' ``last_round_stats``."""
    def share(names):
        return {n: split.span_idle_s.get(n, 0.0) / rounds for n in names}

    dispatch, host = share(DISPATCH), share(HOST)
    harness = sum(v for k, v in split.span_idle_s.items()
                  if not k.startswith("idkd.")) / rounds
    out = {"round_idle_s.dispatch": {"value": sum(dispatch.values()),
                                     **dispatch},
           "round_idle_s.host": {"value": sum(host.values()), **host,
                                 "unattributed_s": harness}}
    if round_stats:
        total = {k: sum(st[k] for st in round_stats)
                 for k in ("compile_path_s", "compiles", "cache_hits")}
        out["round_compile_path_s"] = {
            "value": total["compile_path_s"] / rounds,
            "compiles": total["compiles"], "cache_hits": total["cache_hits"]}
    return out
