"""The program's own records in a profiler trace, reduced beside the
benchmark's.

``bench/tracing.py``, which the accepted per-layer metrics read, keeps the
operations of each device plane by name and the benchmark's ``bench:``
spans.  The program records more in the same file:

* its ``repro.`` host spans (``repro/runtime/tracing.py``), with their
  stats: ``repro.evaluate.batch`` around each ``evaluate_batch`` call,
  tiled by ``repro.evaluate.prepare``, ``.dispatch`` and ``.writeback``,
  all four carrying the call's number as ``call``; ``repro.gc`` around each
  collection, with its ``generation``;
* the named scopes of its jitted sweep (``fit``, ``sweep/slab``,
  ``sweep/fill``, ``tail``) in the ``tf_op`` stat of each operation's event
  metadata, e.g. ``jit(_evaluate_batch_jit)/vmap(sweep)/vmap(fill)/while/
  body/mul``.  ``jax.profiler.ProfileData`` does not expose event
  metadata, so :func:`op_scopes` reads it from the file's wire format.

:func:`load_trace` and :func:`summarize` take the place of their namesakes
in ``bench/tracing.py`` and give a :class:`ProgramTrace`: the same summary,
over the benchmark's and the program's spans, plus the stats and the scope
path of each operation.  Its idle gaps are labelled, as in
``bench/tracing.py``, by the innermost span open at their middle, with the
prefix taken off: ``repro.`` is as long as ``bench:``, so a gap inside a
call reads ``evaluate.writeback`` or ``gc``.

The device's clock in a TPU trace can sit a millisecond or more off the
host's (:func:`device_clock_offset`), and a phase of a call lasts about
as long, so the program's spans are moved onto the device's clock; the
benchmark's spans stay where ``bench/tracing.py`` puts them, and the
accepted metrics read what it gives them.  The functions at the end
compute per-layer numbers from a :class:`ProgramTrace`, each None where
the program recorded nothing to read.
"""

from __future__ import annotations

import bisect
import gzip
import re
from collections import Counter
from dataclasses import dataclass, field

from bench import tracing

PROGRAM_PREFIX = "repro."
CALL_SPAN = PROGRAM_PREFIX + "evaluate.batch"
GC_SPAN = PROGRAM_PREFIX + "gc"


@dataclass
class ProgramTrace(tracing.TraceSummary):
    stats: dict[tuple, dict] = field(default_factory=dict)  # span -> its stats
    # device -> [(scope path as a tuple of names, start, end)], unclipped
    scoped: dict[str, list[tuple[tuple, int, int]]] = field(default_factory=dict)
    clock_offset: int = 0  # ns taken off the program's spans (load_trace)

    @property
    def device_window(self) -> tuple[int, int]:
        """The window on the device's clock, where the program's spans lie."""
        return self.window[0] - self.clock_offset, self.window[1] - self.clock_offset

    def spans_in_window(self, name: str) -> list[tuple[int, int]]:
        """The program's spans ``name`` that lie in the window."""
        w0, w1 = self.device_window
        return [(a, b) for a, b in self.spans_named(name) if w0 <= a and b <= w1]

    def host_s(self, name: str) -> float:
        """Seconds of the spans ``name`` in the window during which no
        device operation ran: their wall time less the device-busy time
        inside them."""
        spans = self.spans_in_window(name)
        wall = sum(b - a for a, b in spans) * 1e-9
        return wall - sum(self.busy_within(a, b) for a, b in spans)

    def scope_busy_s(self, *names: str) -> float | None:
        """Device seconds in the window covered by the operations whose
        scope path holds ``names`` in that order, averaged like
        :attr:`busy_s`; None when no operation holds them.  A union, so a
        ``while`` and the body operations it covers count once."""
        per, found = [], False
        for dev, ivs in self.busy.items():
            if not ivs:
                continue
            hits = [(a, b) for path, a, b in self.scoped.get(dev, ()) if holds(path, names)]
            found = found or bool(hits)
            per.append(tracing.length(tracing.clip(tracing.merge(hits), *self.device_window)))
        return sum(per) / len(per) * 1e-9 if found else None


def scope_path(tf_op: str) -> tuple[str, ...]:
    """The names of a ``tf_op`` path with its transforms unwrapped:
    ``jit(f)/vmap(sweep)/vmap(fill)/while`` gives ``("jit", "f", "vmap",
    "sweep", "vmap", "fill", "while")``.  A fused operation joins the
    paths of its parts with ``;``, and holds the names of each."""
    return tuple(t for t in re.split(r"[/();]+", tf_op) if t)


def holds(path: tuple[str, ...], names) -> bool:
    """Whether ``path`` holds ``names`` in that order."""
    rest = iter(path)
    return all(n in rest for n in names)


def attribute(events, scopes: dict) -> list[tuple[tuple, int, int]]:
    """``(path, start, end)`` of each event of one device that has a scope
    path (``scopes``: event -> ``tf_op``).  On the TPU a ``while`` carries
    no ``tf_op``, and its event covers the operations of its loop, whose
    paths run through the loop's own: ``.../fill/while/body/...``.  An
    event with no path takes that of the loop its covered operations run
    in, up to their first ``while`` (the most common one, where a few
    operations moved into the loop from around it)."""
    paths: dict[str, tuple] = {}
    scoped, bare = [], []
    for ev in events:
        tf_op = scopes.get(ev)
        if tf_op:
            path = paths.get(tf_op) or paths.setdefault(tf_op, scope_path(tf_op))
            scoped.append((path, ev[1], ev[2]))
        else:
            bare.append(ev)
    scoped.sort(key=lambda x: x[1])
    starts = [a for _, a, _ in scoped]
    out = list(scoped)
    for _, a, b in bare:
        lo, hi = bisect.bisect_left(starts, a), bisect.bisect_left(starts, b)
        loops = Counter(
            p[: p.index("while") + 1] for p, _, end in scoped[lo:hi] if end <= b and "while" in p
        )
        if loops:
            out.append((loops.most_common(1)[0][0], a, b))
    return out


def summarize(device_events, spans, stats=None, scopes=None, clock_offset=0) -> ProgramTrace:
    """:func:`bench.tracing.summarize` over the benchmark's and the
    program's spans, with ``stats`` (``{span: {stat: value}}``), ``scopes``
    (``{device: {event: tf_op}}``) and the shift of the program's spans
    onto the device clock kept beside it."""
    base = tracing.summarize(device_events, spans)
    scoped = {
        dev: attribute(events, (scopes or {}).get(dev, {}))
        for dev, events in device_events.items()
    }
    return ProgramTrace(
        window=base.window, busy=base.busy, ops=base.ops, spans=base.spans,
        stats=dict(stats or {}), scoped=scoped, clock_offset=clock_offset,
    )


def load_trace(path: str):
    """``(device_events, spans, stats, scopes, clock_offset)`` from one
    ``.xplane.pb`` file (or its gzip), as :func:`summarize` takes them: the
    device events and the benchmark's spans as ``bench/tracing.py`` reads
    them, the program's spans moved onto the device clock, their stats,
    each operation's ``tf_op``, and the shift in nanoseconds
    (:func:`device_clock_offset`)."""
    from jax.profiler import ProfileData

    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rb") as f:
        raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    tf_ops = op_scopes(raw)
    device_events: dict[str, list] = {}
    scopes: dict[str, dict] = {}
    spans, program = [], []
    modules, enqueued, completed = [], {}, {}
    for plane in data.planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            events = device_events.setdefault(plane.name, [])
            named = tf_ops.get(plane.name, {})
            scoped = scopes.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    for ev in line.events:
                        start = int(ev.start_ns)
                        flow = dict(ev.stats).get(FLOW_IN)
                        modules.append((flow, start, start + int(ev.duration_ns)))
                if line.name not in tracing.OP_LINES:
                    continue
                for ev in line.events:
                    start = int(ev.start_ns)
                    event = (ev.name.split(" = ", 1)[0], start, start + int(ev.duration_ns))
                    events.append(event)
                    if named.get(ev.name):
                        scoped[event] = named[ev.name]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    start = int(ev.start_ns)
                    if ev.name.startswith(tracing.SPAN_PREFIX):
                        spans.append((ev.name, start, start + int(ev.duration_ns)))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        program.append((ev.name, start, start + int(ev.duration_ns), dict(ev.stats)))
                    elif ev.name == ENQUEUE:
                        flow = dict(ev.stats).get(FLOW_OUT)
                        enqueued[flow] = max(start, enqueued.get(flow, start))
                    elif ev.name == COMPLETE:
                        flow = dict(ev.stats).get(FLOW_IN)
                        completed[flow] = min(start, completed.get(flow, start))
    offset = device_clock_offset(modules, enqueued, completed)
    stats = {}
    for name, a, b, st in program:
        span = (name, a - offset, b - offset)
        spans.append(span)
        stats[span] = st
    return device_events, spans, stats, scopes, offset


# The device's clock in a TPU trace can sit a millisecond or more off the
# host's, more than a phase of a call lasts.  Each execution of a program
# (an event of the device's MODULE_LINE) is tied by a flow id to the host
# event that enqueued it and to the one that ran its completion callbacks.
MODULE_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"
FLOW_OUT, FLOW_IN = "_p", "_c"


def device_clock_offset(modules, enqueued: dict, completed: dict) -> int:
    """Nanoseconds to add to the device's recorded times to put them on
    the host's clock: the least shift (0 where the record is consistent)
    that starts each program execution after the host enqueued it and ends
    it before the host ran its completion callbacks.  ``modules`` holds
    ``(flow, start, end)`` of each execution, ``enqueued`` and
    ``completed`` the host start of the events of each flow; where the two
    bounds disagree, the enqueue holds."""
    late = max((enqueued[f] - a for f, a, _ in modules if f in enqueued), default=0)
    early = min((completed[f] - b for f, _, b in modules if f in completed), default=0)
    if late > 0:
        return late
    return min(early, 0)


# -- the wire format of an XSpace, as far as the operations' scopes need it --
#
# XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map<int64,
# XEventMetadata>), .stat_metadata = 5 (map<int64, XStatMetadata>); a map
# entry holds its key in 1 and its value in 2; XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1, .str_value = 5,
# .ref_value = 7 (the id of a stat metadata whose name is the string).


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one message: an int for a
    varint, a memoryview for a length-delimited field, None for a fixed
    one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _entry(buf):
    """(key, value) of one map entry."""
    got = dict(_fields(buf))
    return got.get(1, 0), got.get(2, b"")


def op_scopes(raw: bytes) -> dict[str, dict[str, str]]:
    """``{device plane: {operation's event name: its tf_op}}`` from a
    serialized XSpace: the ``tf_op`` stat of each operation's event
    metadata, where the name scopes of the jitted program land."""
    out = {}
    for number, plane in _fields(memoryview(raw)):
        if number != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                metas.append(v)
            elif f == 5:
                key, meta = _entry(v)
                stat_names[key] = next((bytes(x).decode() for g, x in _fields(meta) if g == 2), "")
        if not tracing.DEVICE_PLANE.match(name):
            continue
        tf_op_id = next((k for k, n in stat_names.items() if n == "tf_op"), None)
        named = out.setdefault(name, {})
        for entry in metas:
            op, tf_op = "", None
            for f, v in _fields(_entry(entry)[1]):
                if f == 2:
                    op = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op_id:
                        tf_op = bytes(stat[5]).decode() if 5 in stat else stat_names.get(stat.get(7))
            if tf_op:
                named[op] = tf_op
    return out


# -- per-layer numbers of the program's records ----------------------------------


def host_ms_per_call(trace: ProgramTrace, phase: str) -> float | None:
    """Host milliseconds of the phase ``repro.evaluate.<phase>`` (its wall
    time that no device operation overlaps) per ``evaluate_batch`` call."""
    calls = trace.spans_in_window(CALL_SPAN)
    if not calls:
        return None
    return 1e3 * trace.host_s(f"{PROGRAM_PREFIX}evaluate.{phase}") / len(calls)


def device_fill_ms_per_call(trace: ProgramTrace) -> float | None:
    """Device milliseconds of the sweep's fill (operations under the scopes
    ``sweep`` then ``fill``) per ``evaluate_batch`` call."""
    calls = trace.spans_in_window(CALL_SPAN)
    fill = trace.scope_busy_s("sweep", "fill")
    if not calls or fill is None:
        return None
    return 1e3 * fill / len(calls)


def gc_pause_share(trace: ProgramTrace) -> float | None:
    """Percent of the window in which Python's cyclic collector ran: the
    union of the ``repro.gc`` spans, clipped to the window."""
    if not any(n.startswith(PROGRAM_PREFIX) for n, _, _ in trace.spans):
        return None  # a program that records no spans
    gc = tracing.clip(tracing.merge(trace.spans_named(GC_SPAN)), *trace.device_window)
    return 100.0 * tracing.length(gc) * 1e-9 / trace.window_s
