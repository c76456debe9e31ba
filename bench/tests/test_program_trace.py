"""The reduction of the program's own records (``bench/program_trace.py``)
on events written by hand and on traces recorded on a TPU v5 lite."""

import gzip
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import core, program_trace as pt, tracing

W = tracing.WINDOW_SPAN
DATA = Path(__file__).parent / "data"
ACCEPTED = ("device_idle_share.sweep", "device_ms_per_call.sweep", "host_ms_per_call.sweep")
PHASES = ("repro.evaluate.prepare", "repro.evaluate.dispatch", "repro.evaluate.writeback")


def _new_numbers(s):
    return {
        "host_prepare": pt.host_ms_per_call(s, "prepare"),
        "host_dispatch": pt.host_ms_per_call(s, "dispatch"),
        "host_writeback": pt.host_ms_per_call(s, "writeback"),
        "device_fill": pt.device_fill_ms_per_call(s),
        "gc_pause_share": pt.gc_pause_share(s),
    }


def _written():
    # a while (10-50) that carries no scope covers two operations of its
    # loop and one moved into it from the sweep around it; a slab
    # operation; an unscoped copy that covers nothing
    device = {
        "/device:TPU:0": [
            ("%while.1", 10, 50),
            ("%fusion.1", 12, 20),
            ("%fusion.2", 30, 40),
            ("%fusion.4", 42, 44),
            ("%fusion.3", 60, 70),
            ("%copy.1", 80, 85),
        ]
    }
    fill = "jit(f)/vmap(sweep)/vmap(fill)/while/body/mul:"
    scopes = {
        "/device:TPU:0": {
            ("%fusion.1", 12, 20): fill,
            ("%fusion.2", 30, 40): fill.replace("body", "cond"),
            ("%fusion.4", 42, 44): "jit(f)/vmap(sweep)/vmap()/max:",
            ("%fusion.3", 60, 70): "jit(f)/vmap(sweep)/slab/vmap()/add:",
        }
    }
    spans = [
        (W, 0, 100),
        ("bench:sweep.evaluate_batch", 0, 100),
        ("repro.evaluate.batch", 1, 99),
        ("repro.evaluate.prepare", 1, 5),
        ("repro.evaluate.dispatch", 5, 8),
        ("repro.evaluate.writeback", 8, 99),
        ("repro.gc", 86, 98),
    ]
    stats = {("repro.gc", 86, 98): {"generation": 2}}
    return pt.summarize(device, spans, stats, scopes)


def test_scope_path_unwraps_transforms():
    path = pt.scope_path("jit(f)/vmap(sweep)/vmap(vmap(fill))/while/body/mul:")
    assert path == ("jit", "f", "vmap", "sweep", "vmap", "vmap", "fill", "while", "body", "mul:")
    assert pt.holds(path, ("sweep", "fill")) and not pt.holds(path, ("fill", "sweep"))
    fused = pt.scope_path("jit(f)/vmap(fill)/while;jit(f)/vmap(tail)/max:")
    assert pt.holds(fused, ("fill",)) and pt.holds(fused, ("tail",))


def test_scoped_busy_counts_a_while_and_its_body_once():
    s = _written()
    scoped = {(a, b): path for path, a, b in s.scoped["/device:TPU:0"]}
    # the while takes the path of the loop its operations run in
    assert scoped[(10, 50)] == ("jit", "f", "vmap", "sweep", "vmap", "fill", "while")
    assert (80, 85) not in scoped
    assert s.scope_busy_s("sweep", "fill") == pytest.approx(40e-9)  # not 40 + 8 + 10
    assert s.scope_busy_s("sweep", "slab") == pytest.approx(10e-9)
    assert s.scope_busy_s("tail") is None


def test_idle_gaps_prefer_the_innermost_program_span():
    s = _written()
    # gaps 85-100 (gc inside writeback inside the call spans), 0-10
    # (dispatch opens at its middle), 50-60 and 70-80 (writeback)
    gaps = s.idle_gaps()
    assert [g[0] for g in gaps] == ["gc", "evaluate.dispatch", "evaluate.writeback", "evaluate.writeback"]
    assert [round(g[1] * 1e9) for g in gaps] == [15, 10, 10, 10]
    assert s.stats[("repro.gc", 86, 98)] == {"generation": 2}


def test_program_numbers_on_written_events():
    read = _new_numbers(_written())
    # one call; device busy 10-50, 60-70, 80-85
    assert read["host_prepare"] == pytest.approx(4e-6)
    assert read["host_dispatch"] == pytest.approx(3e-6)
    assert read["host_writeback"] == pytest.approx((91 - 40 - 10 - 5) * 1e-6)
    assert read["device_fill"] == pytest.approx(40e-6)
    assert read["gc_pause_share"] == pytest.approx(12.0)


def test_device_clock_offset_is_the_least_consistent_shift():
    # flow, device start, device end; host enqueue and completion starts
    modules = [(1, 100, 150), (2, 300, 320)]
    # the device's record sits early: enqueued 30 and 10 after it started
    assert pt.device_clock_offset(modules, {1: 130, 2: 310}, {1: 400, 2: 500}) == 30
    # late: completed 20 before the recorded end
    assert pt.device_clock_offset(modules, {1: 50, 2: 250}, {1: 130, 2: 400}) == -20
    # consistent, or nothing to tie the clocks by
    assert pt.device_clock_offset(modules, {1: 90, 2: 290}, {1: 160, 2: 330}) == 0
    assert pt.device_clock_offset(modules, {}, {}) == 0
    # bounds that disagree: the enqueue holds
    assert pt.device_clock_offset(modules, {1: 130}, {2: 310}) == 30


def test_program_spans_share_the_device_clock():
    """In the recorded trace the device's clock sits 1.18 ms early: each
    program execution starts before the host enqueued it.  Moved onto the
    device clock, each call's sweep runs between the start of its
    ``dispatch`` span and the end of its ``writeback`` span, after the
    jitted call has returned, so no device time is taken off
    ``dispatch``."""
    path = DATA / "sweep_spans_v5e.xplane.pb.gz"
    s = pt.summarize(*pt.load_trace(str(path)))
    assert s.clock_offset == 1178933
    data = ProfileData.from_serialized_xspace(gzip.open(path).read())
    sweeps = sorted(
        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
        for plane in data.planes
        if tracing.DEVICE_PLANE.match(plane.name)
        for line in plane.lines
        if line.name == pt.MODULE_LINE
        for ev in line.events
        if ev.name.startswith("jit__evaluate_batch_jit")
    )
    dispatch = s.spans_named("repro.evaluate.dispatch")
    writeback = s.spans_named("repro.evaluate.writeback")
    assert len(sweeps) == len(dispatch) == len(writeback) == 2
    for (a, b), d, w in zip(sweeps, sorted(dispatch), sorted(writeback)):
        assert d[0] <= d[1] <= a and b <= w[1]
    wall = sum(b - a for a, b in s.spans_in_window("repro.evaluate.dispatch"))
    calls = len(s.spans_in_window(pt.CALL_SPAN))
    assert pt.host_ms_per_call(s, "dispatch") == pytest.approx(wall * 1e-6 / calls, rel=1e-12)


def _accepted(s):
    run = core.Run(cell=core.load_cell("sweep.e7-4830v3-4s.table1"), trace=s)
    return {m: core.metric_reader(m)(run) for m in ACCEPTED}


def test_trace_without_program_records_reads_as_before():
    """The trace recorded before the program had spans or scopes: through
    this reduction the accepted metrics read what ``bench/tracing.py``
    gives them, and the program's numbers read nothing."""
    path = str(DATA / "sweep_v5e.xplane.pb.gz")
    s = pt.summarize(*pt.load_trace(path))
    assert _accepted(s) == {
        "device_idle_share.sweep": pytest.approx(95.08890948922173, rel=1e-12),
        "device_ms_per_call.sweep": pytest.approx(0.9145676666666667, rel=1e-12),
        "host_ms_per_call.sweep": pytest.approx(17.215689, rel=1e-12),
    }
    assert _accepted(s) == pytest.approx(_accepted(tracing.summarize(*tracing.load_trace(path))), rel=1e-12)
    assert all(v is None for v in _new_numbers(s).values())


def test_recorded_tpu_trace_with_program_records():
    """Two sweep calls traced on a TPU v5 lite with the program's spans and
    scopes (the profiler's own file, gzipped): each call's phases nest in
    its span, share its ``call`` and tile it; every number of them reads;
    the fill's ``while``, which carries no ``tf_op`` of its own, counts to
    the fill; and ``bench/tracing.py`` reads the file as before."""
    path = str(DATA / "sweep_spans_v5e.xplane.pb.gz")
    device, spans, stats, scopes, offset = pt.load_trace(path)
    s = pt.summarize(device, spans, stats, scopes, offset)
    calls = [sp for sp in spans if sp[0] == pt.CALL_SPAN]
    assert len(calls) == 2
    for call in calls:
        kids = sorted((sp for sp in spans if sp[0] in PHASES and stats[sp]["call"] == stats[call]["call"]),
                      key=lambda sp: sp[1])
        assert [sp[0] for sp in kids] == list(PHASES)
        assert call[1] <= kids[0][1] and kids[-1][2] <= call[2]
        assert (call[2] - call[1]) - sum(b - a for _, a, b in kids) < 0.05 * (call[2] - call[1])
    read = _new_numbers(s)
    assert all(isinstance(v, float) for v in read.values()), read
    accepted = _accepted(s)
    host = read["host_prepare"] + read["host_dispatch"] + read["host_writeback"]
    assert 0.9 * accepted["host_ms_per_call.sweep"] <= host <= accepted["host_ms_per_call.sweep"]
    assert 0 < read["device_fill"] <= accepted["device_ms_per_call.sweep"]
    (dev,) = device
    names = {(a, b): n for n, a, b in device[dev]}
    fill_whiles = [(a, b) for path, a, b in s.scoped[dev]
                   if names[(a, b)].startswith("%while") and pt.holds(path, ("sweep", "fill"))]
    assert fill_whiles and not any(scopes[dev].get((names[iv], *iv)) for iv in fill_whiles)
    assert accepted == pytest.approx(_accepted(tracing.summarize(*tracing.load_trace(path))), rel=1e-12)
