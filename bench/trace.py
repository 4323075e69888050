"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers.

The trace holds planes; a TPU chip is a plane named ``/device:TPU:<i>``
whose ``XLA Ops`` line lists every operation that ran on it, with start
and duration in nanoseconds on the host's clock. The harness's own
``jax.profiler.TraceAnnotation`` spans (names starting ``bench.``) sit on
the host plane's thread lines. From these the reduction computes:

* busy time: the union of the operation intervals inside the traced
  window, per chip, and its mean over the chips;
* per-kernel time: the summed device time of the operations whose name
  or whose string stats name the kernel (a Mosaic call keeps its
  kernel's name in its stats);
* collective time: the summed device time of collective operations, and
  the part of it during which no other operation ran on that chip;
* idle gaps: the stretches of chip 0's window with no operation,
  labelled by the innermost harness annotation open at the gap's middle;
* the operations that took most device time.
"""
from __future__ import annotations

import collections
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench."
WINDOW_ANNOTATION = "bench.window"
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all")
TOP = 10


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, str] = field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


def _planes_from_profile(pd) -> List[Plane]:
    out = []
    for plane in pd.planes:
        lines: Dict[str, List[Event]] = collections.defaultdict(list)
        for line in plane.lines:
            for e in line.events:
                stats = {str(k): str(v) for k, v in e.stats}
                lines[line.name].append(
                    Event(e.name, float(e.start_ns), float(e.duration_ns),
                          stats))
        out.append(Plane(plane.name, dict(lines)))
    return out


def read_xplane(path: Path) -> List[Plane]:
    from jax.profiler import ProfileData
    return _planes_from_profile(ProfileData.from_file(str(path)))


def read_text_proto(text: str) -> List[Plane]:
    """An ``XSpace`` written as a text proto (the tests' synthetic traces)."""
    from jax.profiler import ProfileData
    return _planes_from_profile(ProfileData.from_text_proto(text))


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _merge(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _clip(intervals, lo: float, hi: float):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            yield a, b


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _subtract(intervals, cover) -> List[Tuple[float, float]]:
    """``intervals`` minus the merged ``cover`` intervals."""
    out = []
    cover = _merge(cover)
    for a, b in intervals:
        cur = a
        for c, d in cover:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def is_collective(e: Event) -> bool:
    text = e.name.lower()
    return any(c in text for c in COLLECTIVES)


def names_kernel(e: Event, kernel: str) -> bool:
    return kernel in e.name or any(kernel in v for v in e.stats.values())


@dataclass
class TraceSummary:
    chips: int
    window_s: float
    busy_s: float                       # mean over chips
    kernel_s: Dict[str, float]          # summed over chips
    kernel_calls: Dict[str, int]
    collective_s: float                 # summed over chips
    collective_exposed_s: float         # summed over chips
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _annotations(planes: Sequence[Plane], prefix: str) -> List[Event]:
    spans = []
    for p in planes:
        if DEVICE_PLANE.match(p.name):
            continue
        for events in p.lines.values():
            spans.extend(e for e in events if e.name.startswith(prefix))
    return spans


def _label(spans: List[Event], t: float) -> str:
    """The innermost (shortest) harness span open at time ``t``."""
    open_ = [s for s in spans if s.start_ns <= t < s.end_ns
             and s.name != WINDOW_ANNOTATION]
    if not open_:
        return "outside any harness span"
    return min(open_, key=lambda s: s.dur_ns).name


def reduce_trace(planes: Sequence[Plane], kernels: Sequence[str] = (),
                 prefix: str = ANNOTATION_PREFIX) -> TraceSummary:
    """Reduce the planes of one trace. The traced window is the harness's
    ``bench.window`` span; without one, the span of all harness spans."""
    spans = _annotations(planes, prefix)
    windows = [s for s in spans if s.name == WINDOW_ANNOTATION]
    if windows:
        lo = min(s.start_ns for s in windows)
        hi = max(s.end_ns for s in windows)
    elif spans:
        lo = min(s.start_ns for s in spans)
        hi = max(s.end_ns for s in spans)
    else:
        raise ValueError("the trace holds no harness span to bound "
                         "the window")
    devices = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = [d for d in devices if d.lines.get(OPS_LINE)]
    if not devices:
        raise ValueError("the trace holds no device plane with operations")

    busy = []
    kernel_s = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    coll_s = coll_exposed = 0.0
    per_op: Dict[str, float] = collections.Counter()
    gaps: List[Tuple[str, float]] = []
    for i, dev in enumerate(devices):
        ops = [e for e in dev.lines[OPS_LINE]
               if e.end_ns > lo and e.start_ns < hi]
        clipped = {id(e): list(_clip([(e.start_ns, e.end_ns)], lo, hi))
                   for e in ops}
        merged = _merge(iv for e in ops for iv in clipped[id(e)])
        busy.append(_length(merged))
        for e in ops:
            t = _length(clipped[id(e)])
            per_op[e.name] += t
            for k in kernels:
                if names_kernel(e, k):
                    kernel_s[k] += t
                    kernel_calls[k] += 1
        coll = [iv for e in ops if is_collective(e)
                for iv in clipped[id(e)]]
        other = [iv for e in ops if not is_collective(e)
                 for iv in clipped[id(e)]]
        coll_merged = _merge(coll)
        coll_s += _length(coll_merged)
        coll_exposed += _length(_subtract(coll_merged, other))
        if i == 0:
            idle = _subtract([(lo, hi)], merged)
            gaps = [(_label(spans, (a + b) / 2), (b - a) / 1e9)
                    for a, b in idle]
    window_s = (hi - lo) / 1e9
    chips = len(devices)
    return TraceSummary(
        chips=chips, window_s=window_s,
        busy_s=sum(busy) / chips / 1e9,
        kernel_s={k: v / 1e9 for k, v in kernel_s.items()},
        kernel_calls=kernel_calls,
        collective_s=coll_s / 1e9, collective_exposed_s=coll_exposed / 1e9,
        device_ops=[(k, v / 1e9) for k, v in per_op.most_common(TOP)],
        idle_gaps=sorted(gaps, key=lambda g: -g[1])[:TOP])


def summarize_file(path: Path, kernels: Sequence[str] = ()
                   ) -> TraceSummary:
    return reduce_trace(read_xplane(path), kernels)


def kernel_time(summary: TraceSummary, kernel: str) -> Optional[float]:
    """Device seconds of ``kernel``, or None where it never ran."""
    if not summary.kernel_calls.get(kernel):
        return None
    return summary.kernel_s[kernel]
