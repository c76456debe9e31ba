"""The program's own trace: spans on the host path of ``evaluate_batch``,
named scopes in its jitted sweep, and the collector hook
(``repro.runtime.tracing``)."""

import contextlib
import gc
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.numa import E5_2630_V3
from repro.core.numa.benchmarks import benchmark_workload
from repro.core.numa.evaluate import (
    _evaluate_batch_jit,
    _stack_workloads,
    _support_arrays,
    evaluate_batch,
    sweep_placements,
    thread_class_starts,
)
from repro.core.numa.simulator import simulate_grouped_batch
from repro.core.numa.workload import Workload

PHASES = ("repro.evaluate.prepare", "repro.evaluate.dispatch", "repro.evaluate.writeback")


@pytest.fixture(scope="module")
def sweep():
    workloads = [benchmark_workload("CG", 8), benchmark_workload("Page rank", 8)]
    placements = sweep_placements(E5_2630_V3, 8)
    keys = jnp.stack([jax.random.PRNGKey(5), jax.random.PRNGKey(6)])
    return workloads, placements, keys


def _call(sweep):
    workloads, placements, keys = sweep
    out = evaluate_batch(E5_2630_V3, workloads, placements, noise_std=0.02, keys=keys)
    return jax.block_until_ready(out)


def _program_spans(trace_dir, work):
    """``[(name, start_ns, end_ns, stats)]`` of the ``repro.`` host events
    recorded while ``work()`` ran under the profiler, Python tracer off."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return [
        (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns), dict(ev.stats))
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("repro.")
    ]


def test_evaluate_batch_spans_tile_the_call(sweep, tmp_path):
    _call(sweep)  # compile outside the trace
    gc.disable()  # no collection between two phases
    try:
        spans = _program_spans(tmp_path, lambda: _call(sweep))
    finally:
        gc.enable()
    (batch,) = [sp for sp in spans if sp[0] == "repro.evaluate.batch"]
    _, b0, b1, stats = batch
    assert stats["workloads"] == 2 and stats["placements"] == 9
    children = sorted((sp for sp in spans if sp[0] in PHASES), key=lambda sp: sp[1])
    assert [sp[0] for sp in children] == list(PHASES)
    assert all(sp[3]["call"] == stats["call"] for sp in children)
    assert b0 <= children[0][1] and children[-1][2] <= b1
    assert all(a[2] <= b[1] for a, b in zip(children, children[1:]))
    # the parent's self time (outside its children) is the return alone
    self_ns = (b1 - b0) - sum(e - s for _, s, e, _ in children)
    assert self_ns < 0.05 * (b1 - b0)


def test_writeback_fetches_once_and_counts_its_misses(sweep, tmp_path, monkeypatch):
    """A call with fresh keys files every workload (``misses`` = the
    workload count); the same keys again file none.  The fetch and the
    inserts lie inside the writeback."""
    from repro.core.numa import evaluate as ev

    workloads, placements, _ = sweep
    _call(sweep)
    monkeypatch.setattr(ev, "_SIG_CACHE", {})
    fresh = jnp.stack([jax.random.PRNGKey(7001), jax.random.PRNGKey(7002)])

    def twice():
        for _ in range(2):
            jax.block_until_ready(
                evaluate_batch(E5_2630_V3, workloads, placements, noise_std=0.02, keys=fresh)
            )

    spans = _program_spans(tmp_path, twice)
    by_call = {}
    for sp in spans:
        by_call.setdefault(sp[3].get("call"), {})[sp[0]] = sp
    calls = sorted(c for c in by_call if "repro.evaluate.batch" in by_call[c])
    assert len(calls) == 2
    misses = []
    for c in calls:
        d = by_call[c]
        _, w0, w1, _ = d["repro.evaluate.writeback"]
        fetch, insert = d["repro.evaluate.fetch"], d["repro.evaluate.insert"]
        assert w0 <= fetch[1] and fetch[2] <= insert[1] and insert[2] <= w1
        assert fetch[3]["leaves"] == 1  # keys, signatures and misfit, packed
        misses.append(insert[3]["misses"])
    assert misses == [len(workloads), 0]


def test_writeback_pulls_its_rows_in_one_transfer(sweep, monkeypatch):
    """Once the memos are warm, a call with fresh keys moves what the
    signature cache stores to the host in one transfer: one ``np.asarray``
    of the packed rows (2 key words, 2 x 8 signature fields, the misfit
    score and the fill's trips per workload) and no ``jax.device_get``;
    the call still returns device arrays."""
    import types

    from repro.core.numa import evaluate as ev

    workloads, placements, _ = sweep
    _call(sweep)
    gets, pulls = [], []
    device_get = jax.device_get

    def counting_get(x):
        gets.append(len(jax.tree.leaves(x)))
        return device_get(x)

    def counting_asarray(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            pulls.append(a.shape)
        return np.asarray(a, *args, **kwargs)

    counting_np = types.ModuleType("numpy")
    counting_np.__dict__.update(np.__dict__)
    counting_np.asarray = counting_asarray
    monkeypatch.setattr(jax, "device_get", counting_get)
    monkeypatch.setattr(ev, "np", counting_np)
    fresh = jnp.stack([jax.random.PRNGKey(7101), jax.random.PRNGKey(7102)])
    out = evaluate_batch(E5_2630_V3, workloads, placements, noise_std=0.02, keys=fresh)
    assert gets == [] and pulls == [(len(workloads), 2 + 2 * 8 + 1 + 1)]
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(out))


def _one_call_spans(tmp_path, workloads, placements, keys):
    """``{name: stats}`` of the spans of one warm ``evaluate_batch`` call."""

    def call():
        jax.block_until_ready(
            evaluate_batch(E5_2630_V3, workloads, placements, noise_std=0.02, keys=keys)
        )

    call()
    return {sp[0]: sp[3] for sp in _program_spans(tmp_path, call)}


def test_batch_span_counts_buckets_and_slab_rows(sweep, tmp_path):
    """The 9 placements ``[k, 8 - k]`` have 3 supports (node 1 alone, both,
    node 0 alone); CG is one thread class and Page rank's hot and cold
    halves make two, so the slab has 2 classes x 2 nodes = 4 rows."""
    workloads, placements, keys = sweep
    stats = _one_call_spans(tmp_path, workloads, placements, keys)["repro.evaluate.batch"]
    assert thread_class_starts(workloads) == (0, 4)
    assert stats["buckets"] == 3
    assert stats["slab_rows"] == 4


@pytest.mark.parametrize(
    "rows, buckets",
    [([[4, 4], [5, 3], [3, 5]], 1), ([[8, 0], [7, 1]], 2), ([[8, 0], [0, 8], [2, 6]], 3)],
)
def test_buckets_are_the_support_patterns_of_the_placements(sweep, tmp_path, rows, buckets):
    workloads, _, keys = sweep
    placements = jnp.asarray(rows, jnp.int32)
    stats = _one_call_spans(tmp_path, workloads, placements, keys)["repro.evaluate.batch"]
    assert stats["buckets"] == buckets and stats["placements"] == len(rows)


def test_fetch_span_carries_the_fill_trips(sweep, tmp_path):
    """``fill_trips`` is the most trips the sweep fill's while_loop took
    over every workload and placement of the call: at least one, at most
    the fixed-count loop's ``iterations``, and what the fill gives when
    run on its own."""
    workloads, placements, keys = sweep
    spans = _one_call_spans(tmp_path, workloads, placements, keys)
    trips = spans["repro.evaluate.fetch"]["fill_trips"]
    classes = thread_class_starts(workloads)
    fill = jax.jit(
        lambda arrays: simulate_grouped_batch(
            E5_2630_V3, Workload("w", *arrays), placements, thread_classes=classes
        ).fill_trips
    )
    want = max(int(np.asarray(fill(tuple(wl[1:]))).max()) for wl in workloads)
    s, links = E5_2630_V3.n_nodes, E5_2630_V3.topology.n_links
    iterations = min(len(classes) * s, 2 * s + 2 * s * s + links) + 1
    assert 1 <= trips <= iterations
    assert trips == want


def test_fill_counts_its_trips(sweep):
    """The fixed-count loop reports its ``iterations``; the while_loop stops
    when every group froze, no later, with the same rates."""
    workloads, placements, _ = sweep
    classes = thread_class_starts(workloads)
    wl = workloads[1]
    fixed = simulate_grouped_batch(
        E5_2630_V3, wl, placements, thread_classes=classes, early_exit=False
    )
    early = simulate_grouped_batch(E5_2630_V3, wl, placements, thread_classes=classes)
    s, links = E5_2630_V3.n_nodes, E5_2630_V3.topology.n_links
    iterations = min(len(classes) * s, 2 * s + 2 * s * s + links) + 1
    assert np.asarray(fixed.fill_trips).tolist() == [iterations] * len(placements)
    got = np.asarray(early.fill_trips)
    assert got.min() >= 1 and got.max() <= iterations
    np.testing.assert_array_equal(np.asarray(early.group_rates), np.asarray(fixed.group_rates))


def test_calls_are_numbered_in_sequence(sweep, tmp_path):
    _call(sweep)
    spans = _program_spans(tmp_path, lambda: (_call(sweep), _call(sweep)))
    calls = sorted(sp[3]["call"] for sp in spans if sp[0] == "repro.evaluate.batch")
    assert len(calls) == 2 and calls[1] == calls[0] + 1


def _scope_paths(hlo_text: str) -> set[str]:
    """The named scopes of each location in a lowered program, with the
    transforms around them (``vmap(...)``, ``jit(...)``) taken away."""
    scopes = ("fit", "sweep", "slab", "fill", "tail")
    return {
        "/".join(t for t in re.split(r"[/()]+", loc) if t in scopes)
        for loc in re.findall(r'loc\("([^"]*)"', hlo_text)
    }


def _lowered(sweep):
    workloads, placements, keys = sweep
    support, slab_id = _support_arrays(placements)
    return _evaluate_batch_jit.lower(
        E5_2630_V3, _stack_workloads(workloads), placements, support, slab_id,
        keys, 0.02, 0.0, thread_class_starts(workloads), False, None,
    )


def test_sweep_trace_carries_named_scopes(sweep):
    paths = _scope_paths(_lowered(sweep).as_text(debug_info=True))
    assert {"fit", "sweep/slab", "sweep/fill", "tail"} <= paths


def test_named_scopes_leave_outputs_bit_identical(sweep, monkeypatch):
    """The scopes change the program's metadata alone: traced without them
    (``jax.named_scope`` made a no-op), the sweep gives the same bits."""
    with_scopes = _call(sweep)
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        assert _scope_paths(_lowered(sweep).as_text(debug_info=True)) <= {""}
        without = _call(sweep)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for got, want in zip(jax.tree.leaves(with_scopes), jax.tree.leaves(without)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_collections_are_recorded_as_gc_spans(tmp_path):
    import repro.runtime.tracing  # noqa: F401  (installs the hook)

    spans = _program_spans(tmp_path, gc.collect)
    generations = [sp[3].get("generation") for sp in spans if sp[0] == "repro.gc"]
    assert 2 in generations


def test_calls_with_an_iterable_and_a_list_of_placements(sweep):
    """The call's span counts its workloads and placements without
    converting them: an iterable of workloads (read once) and placements
    as nested lists give the outputs of the arrays."""
    workloads, placements, keys = sweep
    want = _call(sweep)
    got = evaluate_batch(
        E5_2630_V3, iter(workloads), np.asarray(placements).tolist(), noise_std=0.02, keys=keys
    )
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
