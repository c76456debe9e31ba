"""The trace reduction on events written by hand, and on a trace recorded
here on the CPU (host spans only)."""

import pytest

from bench import tracing

W = tracing.WINDOW_SPAN


def _summary():
    # window 0..100 ns; device busy 10-30 and 25-40 (merged 10-40) and 60-70
    device = {
        "/device:TPU:0": [
            ("fill", 10, 30),
            ("fill", 25, 40),
            ("slab", 60, 70),
            ("before", -20, -10),  # outside the window: ignored
            ("edge", 95, 120),  # clipped to the window
        ]
    }
    spans = [
        (W, 0, 100),
        ("bench:sweep.evaluate_batch", 5, 45),
        ("bench:sweep.keys", 45, 55),
        ("bench:sweep.evaluate_batch", 55, 90),
    ]
    return tracing.summarize(device, spans)


def test_busy_idle_and_window():
    s = _summary()
    assert s.window == (0, 100)
    assert s.busy["/device:TPU:0"] == [(10, 40), (60, 70), (95, 100)]
    assert s.busy_s == pytest.approx(45e-9)
    assert s.window_s == pytest.approx(100e-9)
    assert s.idle_share == pytest.approx(0.55)


def test_busy_within_spans():
    s = _summary()
    calls = s.spans_named("bench:sweep.evaluate_batch")
    assert calls == [(5, 45), (55, 90)]
    assert s.busy_within(5, 45) == pytest.approx(30e-9)
    assert s.busy_within(55, 90) == pytest.approx(10e-9)


def test_top_ops_sum_device_time_in_the_window():
    ops = dict(_summary().top_ops())
    assert ops["fill"] == pytest.approx(35e-9)  # overlapping events both count
    assert ops["slab"] == pytest.approx(10e-9)
    assert ops["edge"] == pytest.approx(5e-9)
    assert "before" not in ops


def test_idle_gaps_are_labelled_by_the_innermost_open_span():
    gaps = _summary().idle_gaps()
    # gaps: 0-10 (call), 40-60 (keys at 50 is innermost), 70-95 (call)
    assert [g[0] for g in gaps] == ["sweep.evaluate_batch", "sweep.keys", "sweep.evaluate_batch"]
    assert [round(g[1] * 1e9) for g in gaps] == [25, 20, 10]


def test_averaged_over_devices_that_ran():
    device = {"/device:TPU:0": [("a", 0, 50)], "/device:TPU:1": [("a", 0, 30)], "/device:TPU:2": []}
    s = tracing.summarize(device, [(W, 0, 100)])
    assert s.busy_s == pytest.approx(40e-9)


def test_interval_helpers():
    assert tracing.merge([(5, 6), (0, 2), (1, 3), (7, 7)]) == [(0, 3), (5, 6)]
    assert tracing.complement([(2, 3), (5, 9)], 0, 8) == [(0, 2), (3, 5)]
    assert tracing.length(tracing.clip([(0, 10)], 3, 5)) == 2


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tracing.summarize({}, [("bench:other", 0, 1)])


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded with the profiler: the benchmark's annotations come
    back as spans on the trace clock, and a CPU has no device plane."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracing.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(W):
        with jax.profiler.TraceAnnotation("bench:sweep.evaluate_batch"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    device, spans = tracing.load_trace(tracing.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in spans]
    assert names.count(W) == 1 and names.count("bench:sweep.evaluate_batch") == 1
    (_, w0, w1), = [sp for sp in spans if sp[0] == W]
    (_, c0, c1), = [sp for sp in spans if sp[0] != W]
    assert w0 <= c0 < c1 <= w1
    assert device == {}
    s = tracing.summarize(device, spans)
    assert s.busy_s == 0.0 and s.idle_share == 1.0


def test_recorded_tpu_trace():
    """Three 4-socket sweep calls traced on a TPU v5 lite (the profiler's
    own file, gzipped): the reduction finds the chip's operations and the
    benchmark's spans on one clock."""
    from pathlib import Path

    from bench import core

    path = Path(__file__).parent / "data" / "sweep_v5e.xplane.pb.gz"
    device, spans = tracing.load_trace(str(path))
    assert list(device) == ["/device:TPU:0"] and len(device["/device:TPU:0"]) > 1000
    s = tracing.summarize(device, spans)
    calls = s.spans_named("bench:sweep.evaluate_batch")
    assert len(calls) == 3
    (w0, w1), = s.spans_named(W)
    assert all(w0 <= a < b <= w1 for a, b in calls)
    # each call keeps the chip busy for under a millisecond of its ~15 ms
    per_call = [s.busy_within(a, b) for a, b in calls]
    assert all(5e-4 < t < 2e-3 for t in per_call)
    assert 0.5 < s.idle_share < 1.0
    assert s.top_ops(1)[0][0].startswith("%while")
    assert {g[0] for g in s.idle_gaps()} <= {"sweep.evaluate_batch", "sweep.keys", "idle"}
    cell = core.load_cell("sweep.e7-4830v3-4s.table1")
    run = core.Run(cell=cell, trace=s)
    reader = core.metric_reader("device_ms_per_call.sweep")
    assert reader(run) == pytest.approx(1e3 * sum(per_call) / 3)
    host = core.metric_reader("host_ms_per_call.sweep")(run)
    wall = sum(b - a for a, b in calls) * 1e-9
    assert host == pytest.approx(1e3 * (wall - sum(per_call)) / 3)
    assert core.metric_reader("device_idle_share.sweep")(run) == pytest.approx(100 * s.idle_share)
