"""Reduction of a profiler trace to the benchmark's device numbers.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load_trace` reads it with
``jax.profiler.ProfileData`` into plain ``(name, start_ns, end_ns)``
tuples: the operations of each device plane, and the benchmark's own host
spans (``jax.profiler.TraceAnnotation`` events whose name starts with
:data:`SPAN_PREFIX`).  Both sit on the trace's one clock.
:func:`summarize` then works on those tuples alone, so it can be checked
against events written by hand.

* busy time: the union of the operation intervals of a TPU, clipped to
  the traced window, averaged over the devices that ran anything;
* idle share: 1 - busy / window;
* top operations: the summed device time of each operation name;
* idle gaps: the stretches of the window in which no operation ran on the
  first busy device, each labelled with the innermost benchmark span open
  at its middle (``idle`` when none is).
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
# Device lines that hold one event per executed operation.  Other lines of
# a TPU plane (modules, steps, framework name scopes) cover the same time
# again at a coarser grain.
OP_LINES = ("XLA Ops",)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def start(trace_dir: str) -> None:
    """Start the profiler without its Python tracer, which records every
    Python call and slows the host several-fold; the benchmark's own
    annotations are kept."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


@dataclass
class TraceSummary:
    window: tuple[int, int]  # ns, trace clock
    busy: dict[str, list[tuple[int, int]]]  # device -> merged intervals
    ops: dict[str, float]  # operation name -> device seconds in the window
    spans: list[tuple[str, int, int]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Device-busy seconds in the window, averaged over the devices
        that ran at least one operation."""
        per = [length(iv) for iv in self.busy.values() if iv]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def busy_within(self, start: int, end: int) -> float:
        """Device-busy seconds inside ``[start, end)``, averaged like
        :attr:`busy_s`; a lookup in each device's running busy total, so
        the thousands of call spans of a window cost no rescan."""
        per = [
            _busy_before(cov, end) - _busy_before(cov, start)
            for cov in self._coverage()
        ]
        return sum(per) / len(per) * 1e-9 if per else 0.0

    def _coverage(self):
        if not hasattr(self, "_cov"):
            self._cov = []
            for iv in self.busy.values():
                if iv:
                    done = [0]
                    for a, b in iv:
                        done.append(done[-1] + b - a)
                    self._cov.append(([a for a, _ in iv], [b for _, b in iv], done))
        return self._cov

    def spans_named(self, name: str) -> list[tuple[int, int]]:
        return [(a, b) for n, a, b in self.spans if n == name]

    def top_ops(self, k: int = 10) -> list[list]:
        return [
            [name, secs]
            for name, secs in sorted(self.ops.items(), key=lambda kv: -kv[1])[:k]
        ]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The ``k`` longest idle gaps, as ``[label, seconds]``."""
        busy = next((iv for iv in self.busy.values() if iv), [])
        gaps = complement(busy, *self.window)
        gaps.sort(key=lambda g: g[0] - g[1])
        inner = [sp for sp in self.spans if sp[0] != WINDOW_SPAN]
        out = []
        for a, b in gaps[:k]:
            mid = (a + b) // 2
            open_spans = [sp for sp in inner if sp[1] <= mid < sp[2]]
            if open_spans:
                label = min(open_spans, key=lambda sp: sp[2] - sp[1])[0]
                label = label[len(SPAN_PREFIX):]
            else:
                label = "idle"
            out.append([label, (b - a) * 1e-9])
        return out


def _busy_before(cov, t: int) -> int:
    """Busy nanoseconds before ``t`` in merged intervals, given as their
    starts, their ends and the running total of their lengths."""
    starts, ends, done = cov
    i = bisect.bisect_right(starts, t) - 1
    if i < 0:
        return 0
    return done[i] + min(t, ends[i]) - starts[i]


def merge(intervals) -> list[tuple[int, int]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, start: int, end: int) -> list[tuple[int, int]]:
    return [
        (max(a, start), min(b, end))
        for a, b in intervals
        if min(b, end) > max(a, start)
    ]


def length(intervals) -> int:
    return sum(b - a for a, b in intervals)


def complement(intervals, start: int, end: int) -> list[tuple[int, int]]:
    """The parts of ``[start, end)`` that no interval covers."""
    out, at = [], start
    for a, b in clip(merge(intervals), start, end):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < end:
        out.append((at, end))
    return out


def summarize(device_events, spans) -> TraceSummary:
    """``device_events``: ``{device: [(op, start_ns, end_ns), ...]}``;
    ``spans``: benchmark host spans ``[(name, start_ns, end_ns), ...]``,
    one of them :data:`WINDOW_SPAN`."""
    windows = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = min(a for a, _ in windows), max(b for _, b in windows)
    busy = {}
    ops: dict[str, float] = defaultdict(float)
    for dev, events in device_events.items():
        inside = [(n, max(a, w0), min(b, w1)) for n, a, b in events if min(b, w1) > max(a, w0)]
        busy[dev] = merge((a, b) for _, a, b in inside)
        for n, a, b in inside:
            ops[n] += (b - a) * 1e-9
    return TraceSummary(window=(w0, w1), busy=busy, ops=dict(ops), spans=list(spans))


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_trace(path: str):
    """``(device_events, spans)`` from one ``.xplane.pb`` file (or its
    gzip)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    device_events: dict[str, list] = {}
    spans = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = [ln for ln in plane.lines if ln.name in OP_LINES]
            events = device_events.setdefault(plane.name, [])
            for line in lines:
                for ev in line.events:
                    start = int(ev.start_ns)
                    # "%fusion.9 = f32[...] fusion(...)": keep the op's name
                    name = ev.name.split(" = ", 1)[0]
                    events.append((name, start, start + int(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        start = int(ev.start_ns)
                        spans.append((ev.name, start, start + int(ev.duration_ns)))
    return device_events, spans
