"""Integration tests for the §6 evaluation harness (paper-claim anchors)."""

import numpy as np
import pytest

from repro.core.numa import E5_2630_V3, E5_2699_V3
from repro.core.numa.benchmarks import benchmark_workload, suite_names
from repro.core.numa.evaluate import (
    evaluate_accuracy,
    evaluate_stability,
    evaluate_suite,
    sweep_placements,
)


def test_sweep_respects_one_thread_per_core():
    p = np.asarray(sweep_placements(E5_2630_V3, 8))
    assert p.sum(axis=1).tolist() == [8] * len(p)
    assert p.max() <= 8
    assert len(p) == 9  # 0..8 on socket 0


def test_suite_has_23_benchmarks():
    names = suite_names(include_violators=True)
    assert len(names) == 23  # paper Table 1
    assert "Page rank" in names and "EP" in names


def test_noise_free_accuracy_is_exact_for_representable_workloads():
    """With perfect counters and an in-model workload the fit+predict
    pipeline must reproduce measurements exactly — the correctness anchor
    behind the paper's Figure 17."""
    wl = benchmark_workload("Swim", 16)
    res = evaluate_accuracy(E5_2699_V3, wl)
    assert float(np.asarray(res.errors_combined).max()) < 1e-3


def test_violator_has_much_larger_error_than_representable():
    wl_good = benchmark_workload("Swim", 16)
    wl_bad = benchmark_workload("Page rank", 16)
    good = evaluate_accuracy(E5_2699_V3, wl_good)
    bad = evaluate_accuracy(E5_2699_V3, wl_bad)
    assert float(np.asarray(bad.errors_combined).mean()) > 10 * float(
        np.asarray(good.errors_combined).mean() + 1e-6
    )
    # and the §6.2.1 detector ranks them accordingly
    assert float(bad.misfit) > 10 * float(good.misfit)


@pytest.mark.slow
def test_suite_median_error_within_paper_band():
    """Paper §6.2.2: median error 2.34% of bandwidth over thousands of
    measurements.  Our ground truth is in-model by construction (except the
    violator), so the median with realistic counter noise must land *below*
    the paper's 2.34%."""
    r = evaluate_suite(E5_2699_V3, noise_std=0.02)
    assert r.all_errors.size > 1000  # "thousands of measurements"
    assert r.median_error_pct < 2.34
    # errors are not degenerate zeros under noise
    assert r.median_error_pct > 0.01


@pytest.mark.slow
def test_stability_across_machines():
    """Paper Figure 14: mean combined-signature change 6.8%, median 4.2%.
    Our simulated machines differ only through saturation-induced rate
    asymmetries, so changes must be small and below the paper's levels."""
    r = evaluate_stability(E5_2630_V3, E5_2699_V3, noise_std=0.01)
    assert r.mean_combined_pct < 6.8
    assert r.median_combined_pct < 4.2


# ---------------------------------------------------------------------------
# module caches: bounded, LRU, thread-safe (the advisor service calls this
# module from many threads — unbounded or torn caches were real failures)
# ---------------------------------------------------------------------------


def test_signature_cache_is_bounded_with_lru_eviction():
    from repro.core.numa import evaluate as ev

    saved = dict(ev._SIG_CACHE)
    try:
        ev._SIG_CACHE.clear()
        for i in range(ev._SIG_CACHE_MAX + 500):
            ev._cache_insert(("synthetic", i), i)
        assert len(ev._SIG_CACHE) == ev._SIG_CACHE_MAX
        # oldest synthetic keys were evicted, newest survive
        assert ("synthetic", 0) not in ev._SIG_CACHE
        assert ("synthetic", ev._SIG_CACHE_MAX + 499) in ev._SIG_CACHE
        # a hit refreshes recency: touch the current oldest, insert one
        # more, and the touched entry must survive the sweep
        oldest = next(iter(ev._SIG_CACHE))
        assert ev._cache_lookup(oldest) is not None
        ev._cache_insert(("synthetic", "tail"), 0)
        assert oldest in ev._SIG_CACHE
    finally:
        ev._SIG_CACHE.clear()
        ev._SIG_CACHE.update(saved)


@pytest.mark.parametrize("memo", ["stack", "support", "fingerprint"])
def test_workload_and_support_memos_are_bounded(memo):
    import jax.numpy as jnp

    from repro.core.numa import evaluate as ev

    make, memoized, cache = {
        "stack": (
            lambda: [benchmark_workload("CG", 8)], ev._stack_workloads,
            ev._STACK_CACHE,
        ),
        "support": (
            lambda: jnp.asarray(np.asarray([[8 - j, j] for j in range(3)])),
            ev._support_arrays, ev._SUPPORT_CACHE,
        ),
        "fingerprint": (
            lambda: benchmark_workload("CG", 8), ev._workload_fingerprint,
            ev._FINGERPRINT_CACHE,
        ),
    }[memo]
    for _ in range(ev._MEMO_CACHE_MAX + 40):
        memoized(make())
    assert len(cache) <= ev._MEMO_CACHE_MAX
    # memo hit returns the identical value (id-keyed)
    arg = make()
    first = memoized(arg)
    assert memoized(arg) is first


def _digest_fields(wl) -> str:
    import hashlib

    digest = hashlib.blake2b(digest_size=16)
    for field in wl[1:]:
        a = np.asarray(field)
        digest.update(str(a.shape).encode())
        digest.update(str(a.dtype).encode())
        digest.update(a.tobytes())
    return digest.hexdigest()


def test_fingerprint_of_a_jax_workload_is_its_digest():
    from repro.core.numa import evaluate as ev

    wl = benchmark_workload("Swim", 8)
    want = (wl.name, wl.n_threads, _digest_fields(wl))
    assert ev._workload_fingerprint(wl) == want
    assert ev._FINGERPRINT_CACHE[id(wl)] == (wl, want)
    assert ev._workload_fingerprint(wl) == want  # served from the memo


def test_fingerprint_of_a_numpy_workload_follows_in_place_changes():
    """NumPy fields can change under the same workload object, so such a
    workload is digested on every call and never memoized."""
    from repro.core.numa import evaluate as ev
    from repro.core.numa.workload import Workload

    jax_wl = benchmark_workload("CG", 8)
    wl = Workload(jax_wl.name, *(np.array(f) for f in jax_wl[1:]))
    before = ev._workload_fingerprint(wl)
    assert before == ev._workload_fingerprint(jax_wl)
    wl.read_local[0] += 0.125
    after = ev._workload_fingerprint(wl)
    assert after != before
    assert after == (wl.name, wl.n_threads, _digest_fields(wl))
    assert id(wl) not in ev._FINGERPRINT_CACHE


def test_memo_caches_survive_concurrent_hammer():
    import threading

    from repro.core.numa import evaluate as ev

    errors = []

    def worker(seed):
        try:
            for i in range(200):
                ev._memo_put(
                    ev._STACK_CACHE, ev._MEMO_LOCK, ("hammer", seed, i % 80),
                    (None, i), ev._MEMO_CACHE_MAX,
                )
                ev._memo_get(
                    ev._STACK_CACHE, ev._MEMO_LOCK,
                    ("hammer", seed, (i * 13) % 80),
                )
                ev._cache_insert(("hammer-sig", seed, i % 80), i)
                ev._cache_lookup(("hammer-sig", seed, (i * 7) % 80))
        except Exception as exc:  # pragma: no cover
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(ev._STACK_CACHE) <= ev._MEMO_CACHE_MAX
    assert len(ev._SIG_CACHE) <= ev._SIG_CACHE_MAX


def test_enumerate_placements_budget_edges():
    """Core-cap feasibility: the boundary budget yields exactly the full
    machine, zero threads yields the empty placement, and anything beyond
    ``s * cores_per_node`` (or negative) is rejected up front."""
    from repro.core.numa.evaluate import count_placements, enumerate_placements

    m = E5_2630_V3  # 2 nodes x 8 cores
    full = m.n_nodes * m.cores_per_node
    at_cap = np.asarray(enumerate_placements(m, full))
    assert at_cap.shape == (1, m.n_nodes)
    assert at_cap.tolist() == [[m.cores_per_node] * m.n_nodes]
    assert count_placements(m, full) == 1

    empty = np.asarray(enumerate_placements(m, 0))
    assert empty.tolist() == [[0] * m.n_nodes]

    with pytest.raises(ValueError):
        enumerate_placements(m, full + 1)
    with pytest.raises(ValueError):
        enumerate_placements(m, -1)
    # per-node caps hold on a feasible-but-tight budget
    tight = np.asarray(enumerate_placements(m, full - 1))
    assert (tight <= m.cores_per_node).all()
    assert len(tight) == count_placements(m, full - 1) == m.n_nodes


def _two_socket_eight_node_host(package_bw=2.0e9, socket_bw=1.2e9):
    """2 sockets of 4 nodes (2 cores each): 6 + 6 on-package links at one
    capacity, 4 socket links (node i to node i + 4) at another, and the 24
    cross-socket pairs of different index routed over two hops, crossing
    the socket link at their source."""
    from repro.core.numa.machine import MachineSpec
    from repro.core.numa.topology import Topology

    ends = [(b + i, b + j) for b in (0, 4) for i in range(4) for j in range(i + 1, 4)]
    ends += [(i, i + 4) for i in range(4)]
    bw = [package_bw] * 12 + [socket_bw] * 4
    index = {frozenset(e): k for k, e in enumerate(ends)}
    routes = []
    for i in range(8):
        for j in range(8):
            if i == j:
                path = []
            elif i // 4 != j // 4 and i % 4 != j % 4:
                path = [i, i + 4 if i < 4 else i - 4, j]
            else:
                path = [i, j]
            routes.append(tuple(index[frozenset(e)] for e in zip(path[:-1], path[1:])))
    topo = Topology(name="2s8n", n_nodes=8, link_ends=tuple(ends), link_bw=tuple(bw),
                    routes=tuple(routes))
    machine = MachineSpec(
        name="2s8n", sockets=2, cores_per_socket=8, local_read_bw=5e9, local_write_bw=3e9,
        remote_read_bw=1.5e9, remote_write_bw=1.2e9, core_rate=(2.2e9,) * 8, topology=topo,
        hop_attenuation=0.9, nodes_per_socket=4,
    )
    machine.validate()
    return machine


def test_evaluate_batch_on_a_routed_eight_node_host_matches_the_reference():
    """evaluate_batch's grouped fill over a sampled placement space of an
    8-node, 2-socket host with two-hop routes and links of two capacities
    gives the per-thread reference's run bandwidth, placement by placement."""
    import jax
    import jax.numpy as jnp

    from repro.core.numa.evaluate import count_placements, evaluate_batch
    from repro.core.numa.simulator import simulate_reference

    machine = _two_socket_eight_node_host()
    assert machine.topology.hop_matrix().max() == 2
    assert count_placements(machine, 8) > 64
    placements = sweep_placements(machine, 8, max_placements=64, seed=3)
    workloads = [benchmark_workload(name, 8) for name in ("CG", "Swim", "Page rank")]
    batch = evaluate_batch(machine, workloads, placements)
    wide = evaluate_batch(_two_socket_eight_node_host(1e15, 1e15), workloads, placements)
    for b, wl in enumerate(workloads):
        res = jax.vmap(lambda q: simulate_reference(machine, wl, q))(placements)
        want = np.asarray(res.read_flows.sum(axis=(1, 2)) + res.write_flows.sum(axis=(1, 2)))
        np.testing.assert_allclose(np.asarray(batch.total_bw[b]), want, rtol=2e-6)
    # the links bind: with them widened, some placement moves more
    assert bool(jnp.any(wide.total_bw > batch.total_bw * (1 + 1e-3)))
