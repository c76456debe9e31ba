"""The batched multi-socket placement-sweep engine (beyond-paper s >= 2).

Covers the composition enumerator (exactness, budget subsampling, s = 2
reduction to the paper's ``[i, n - i]`` sweep), the ``evaluate_batch``
equivalence with per-placement simulation on a 4-socket machine, the
single-trace guarantee behind ``evaluate_suite``, and the fitted-signature
cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.bwsig import fit_signature, misfit_score, predict_counters
from repro.core.numa import (
    E5_2630_V3,
    E5_2699_V3,
    E7_4830_V3,
    E7_8860_V3,
    make_machine,
    mixed_workload,
    profile_pair,
    simulate,
)
from repro.core.numa.benchmarks import benchmark_workload
from repro.core.numa.evaluate import (
    _evaluate_batch_jit,
    count_placements,
    enumerate_placements,
    evaluate_accuracy,
    evaluate_batch,
    evaluate_suite,
    fitted_signatures,
    sweep_placements,
)

# ---------------------------------------------------------------------------
# enumerator properties
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("machine", [E5_2630_V3, E7_4830_V3, E7_8860_V3])
@pytest.mark.parametrize("n_threads", [1, 8, 16])
def test_enumeration_is_exact_and_valid(machine, n_threads):
    p = np.asarray(enumerate_placements(machine, n_threads, max_placements=400))
    assert p.shape[0] >= 1
    assert (p.sum(axis=1) == n_threads).all()
    assert p.min() >= 0 and p.max() <= machine.cores_per_socket
    # no duplicates (subsampling draws ranks without replacement)
    assert len({tuple(row) for row in p.tolist()}) == p.shape[0]


@pytest.mark.parametrize("n_threads", [1, 5, 8, 12, 16])
def test_s2_reduces_to_legacy_pair_sweep(n_threads):
    """At s = 2 the generalized enumerator must emit exactly the paper's
    ``[i, n - i]`` sweep, in the same order."""
    machine = E5_2630_V3
    cores = machine.cores_per_socket
    lo, hi = max(0, n_threads - cores), min(cores, n_threads)
    legacy = [[i, n_threads - i] for i in range(lo, hi + 1)]
    got = np.asarray(sweep_placements(machine, n_threads)).tolist()
    assert got == legacy


def test_count_matches_enumeration_and_budget_is_deterministic():
    machine = E7_4830_V3
    total = count_placements(machine, 10)
    full = np.asarray(enumerate_placements(machine, 10))
    assert full.shape == (total, 4)
    a = np.asarray(enumerate_placements(machine, 10, max_placements=50, seed=3))
    b = np.asarray(enumerate_placements(machine, 10, max_placements=50, seed=3))
    np.testing.assert_array_equal(a, b)
    assert a.shape == (50, 4)
    # the sample is a subset of the full enumeration
    full_set = {tuple(r) for r in full.tolist()}
    assert all(tuple(r) in full_set for r in a.tolist())


def test_enumerate_rejects_impossible_thread_counts():
    with pytest.raises(ValueError):
        enumerate_placements(E5_2630_V3, 17)


def test_vectorized_unranking_matches_bigint_loop_and_brute_force():
    """The numpy-vectorized unranking emits the exact lexicographic
    enumeration (checked against a brute-force product filter) and the
    per-rank bigint fallback (same table, forced path)."""
    from itertools import product

    from repro.core.numa.evaluate import _composition_table, _unrank_compositions

    s, cap, n = 4, 5, 9
    table = _composition_table(s, cap, n)
    total = table[s][n]
    got = _unrank_compositions(table, range(total), s, cap, n)
    brute = np.asarray(
        [c for c in product(range(cap + 1), repeat=s) if sum(c) == n], np.int32
    )
    np.testing.assert_array_equal(got, brute)  # product() is lexicographic
    # the bigint fallback (huge sentinel in an unused cell flips the int64
    # guard): unrank compositions of n-1 through both paths
    big = tuple(tuple(row) for row in table[:-1]) + (
        tuple(table[-1][:-1]) + (2**70,),
    )
    total2 = table[s][n - 1]
    ranks = [0, 1, total2 // 2, total2 - 1]
    np.testing.assert_array_equal(
        _unrank_compositions(big, ranks, s, cap, n - 1),
        _unrank_compositions(table, ranks, s, cap, n - 1),
    )


@settings(max_examples=20, deadline=None)
@given(
    n_threads=st.integers(1, 32),
    sockets=st.integers(2, 5),
    cores=st.integers(2, 8),
)
def test_property_compositions_sum_and_bound(n_threads, sockets, cores):
    machine = make_machine("prop", sockets=sockets, cores_per_socket=cores)
    if n_threads > sockets * cores:
        with pytest.raises(ValueError):
            enumerate_placements(machine, n_threads)
        return
    p = np.asarray(enumerate_placements(machine, n_threads, max_placements=64))
    assert (p.sum(axis=1) == n_threads).all()
    assert p.min() >= 0 and p.max() <= cores


# ---------------------------------------------------------------------------
# evaluate_batch equivalence with per-placement simulate on 4 sockets
# ---------------------------------------------------------------------------


def _manual_accuracy(machine, workload, placements, key):
    """The seed implementation's per-placement math, written out longhand."""
    k_prof, k_meas = jax.random.split(key)
    sym, asym = profile_pair(machine, workload, key=k_prof)
    sig = fit_signature(sym, asym)
    sig_c = fit_signature(sym, asym, combined=True)
    keys = jax.random.split(k_meas, placements.shape[0])

    rows = []
    for placement, k in zip(placements, keys):
        res = simulate(machine, workload, placement, key=k)
        total = float(res.read_flows.sum() + res.write_flows.sum())
        total = max(total, 1e-9)
        comb_flows = res.read_flows + res.write_flows
        demand = comb_flows.sum(axis=1)
        pred_l, pred_r = predict_counters(sig_c.read, demand, placement)
        err = jnp.concatenate(
            [
                jnp.abs(pred_l - (res.sample.local_read + res.sample.local_write)),
                jnp.abs(pred_r - (res.sample.remote_read + res.sample.remote_write)),
            ]
        )
        rows.append(np.asarray(err) / total)
    return np.stack(rows), sig


def test_evaluate_batch_equals_per_placement_simulate_4socket():
    machine = E7_4830_V3
    wl = benchmark_workload("CG", 16)
    placements = enumerate_placements(machine, 16, max_placements=16, seed=1)
    key = jax.random.PRNGKey(7)

    with jax.disable_jit():
        # eager vs eager: the shared-slab engine computes the same math
        # with batched contractions (structured remote einsums, closed-form
        # counter predictions), so equality holds to float32 round-off
        # rather than bit-for-bit
        batch = evaluate_batch(machine, wl, placements, keys=key)
        manual, manual_sig = _manual_accuracy(machine, wl, placements, key)
        np.testing.assert_allclose(
            np.asarray(batch.errors_combined[0]), manual, atol=1e-6
        )

    # the jitted trace agrees to float tolerance (XLA fusion reorders ops)
    batch_jit = evaluate_batch(machine, wl, placements, keys=key)
    np.testing.assert_allclose(
        np.asarray(batch_jit.errors_combined[0]), manual, atol=1e-5
    )
    # fitted signature round-trips through the batch path too
    sig = jax.tree.map(lambda x: x[0], batch_jit.signatures)
    for got, want in zip(jax.tree.leaves(sig), jax.tree.leaves(manual_sig)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_accuracy_is_noise_free_exact_on_4_and_8_sockets():
    """The §6.2.2 anchor generalized: with perfect counters and an in-model
    workload, predictions must match measurements on any socket count."""
    for machine in (E7_4830_V3, E7_8860_V3):
        wl = benchmark_workload("Swim", machine.cores_per_socket)
        res = evaluate_accuracy(machine, wl, max_placements=40)
        assert float(np.asarray(res.errors_combined).max()) < 2e-3, machine.name


def test_evaluate_suite_uses_single_trace():
    """All benchmarks of a suite evaluation must flow through ONE
    compilation of the batched engine (no per-benchmark retracing)."""
    machine = E5_2699_V3
    before = _evaluate_batch_jit._cache_size()
    r = evaluate_suite(machine, 8, noise_std=0.02, seed=11)
    after = _evaluate_batch_jit._cache_size()
    assert after - before <= 1
    assert len(r.names) == 23
    assert r.all_errors.size == 23 * 9 * 4  # benchmarks x placements x 2s


def test_suite_median_error_on_4socket_machine():
    """The paper's headline protocol on a 4-socket box: ≥500 placements,
    median model error reported and inside the paper's 2.34% band."""
    r = evaluate_suite(
        E7_4830_V3,
        2 * E7_4830_V3.cores_per_socket,
        noise_std=0.02,
        include_violators=False,
        max_placements=30,
    )
    n_placements = count_placements(E7_4830_V3, 2 * E7_4830_V3.cores_per_socket)
    assert n_placements >= 500  # the full sweep space is paper-scale
    assert r.all_errors.size > 1000
    assert 0.0 < r.median_error_pct < 2.34


def test_fitted_signature_cache_hits():
    machine = E5_2630_V3
    wl = mixed_workload("cache-me", 8, read_mix=(0.3, 0.3, 0.2))
    a = fitted_signatures(machine, wl)[0]
    b = fitted_signatures(machine, wl)[0]
    assert a[0] is b[0]  # identical object => served from the cache
    # different noise is a different key
    c = fitted_signatures(machine, wl, noise_std=0.01)[0]
    assert c[0] is not a[0]


def test_evaluate_batch_fills_the_cache_fitted_signatures_reads(monkeypatch):
    """Each fit of an ``evaluate_batch`` call is filed under its profiling
    key, the first half of the split of its base key: ``fitted_signatures``
    with those keys is answered from the cache alone, and the entries are
    the call's own signatures and misfit scores, bit for bit."""
    from repro.core.numa import evaluate as ev

    machine = E7_4830_V3
    workloads = [benchmark_workload(n, 8) for n in ("CG", "Page rank", "EP")]
    placements = sweep_placements(machine, 8, max_placements=32, seed=2)
    keys = jnp.stack([jax.random.PRNGKey(9100 + i) for i in range(3)])
    monkeypatch.setattr(ev, "_SIG_CACHE", {})
    batch = evaluate_batch(
        machine, workloads, placements, noise_std=0.02, keys=keys
    )

    def no_fit(*args, **kwargs):
        raise AssertionError("fitted_signatures missed the cache")

    monkeypatch.setattr(ev, "_fit_batch_jit", no_fit)
    prof_keys = jnp.stack([jax.random.split(k)[0] for k in keys])
    fits = fitted_signatures(machine, workloads, noise_std=0.02, keys=prof_keys)
    for i, (sig, csig, misfit) in enumerate(fits):
        for got, want in (
            (sig, jax.tree.map(lambda x: x[i], batch.signatures)),
            (csig, jax.tree.map(lambda x: x[i], batch.combined_signatures)),
            (misfit, batch.misfit[i]),
        ):
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
                assert np.asarray(g).tobytes() == np.asarray(w).tobytes()


def test_sig_cache_evicts_oldest_and_keeps_hot_keys(monkeypatch):
    """Ordered LRU eviction: filling the cache past its high-water mark
    drops the *oldest* entries, and a key touched mid-fill (LRU hit)
    survives a full eviction cycle instead of being nuked with the rest."""
    from repro.core.numa import evaluate as ev

    monkeypatch.setattr(ev, "_SIG_CACHE", {})
    monkeypatch.setattr(ev, "_SIG_CACHE_MAX", 8)

    def put(i):
        ev._SIG_CACHE[("key", i)] = i
        ev._evict_cache_if_full()

    for i in range(8):
        put(i)
    hot = ("key", 0)
    for i in range(8, 15):  # 7 younger entries; touch the hot key each time
        assert ev._cache_lookup(hot) == 0
        put(i)
    assert hot in ev._SIG_CACHE  # survived a full eviction cycle
    assert len(ev._SIG_CACHE) == 8
    # the oldest untouched keys are the ones that left
    assert ("key", 1) not in ev._SIG_CACHE
    assert ("key", 14) in ev._SIG_CACHE
    assert ev._cache_lookup(("key", 1)) is None


def test_vectorized_link_resources_match_reference_loop():
    """The vectorized per-link charging (endpoint gather + routed-incidence
    matmul) must reproduce a python loop walking every ordered pair's route
    on the glued 8-socket topology."""
    from repro.core.numa.simulator import _resource_tensor, _thread_nodes

    machine = E7_8860_V3
    topo = machine.topology
    n_threads = 16
    rng = np.random.default_rng(0)
    read_unit = jnp.asarray(rng.uniform(0, 1e9, (n_threads, machine.sockets)), jnp.float32)
    write_unit = jnp.asarray(rng.uniform(0, 1e9, (n_threads, machine.sockets)), jnp.float32)
    n_per = jnp.asarray([4, 4, 2, 2, 2, 1, 1, 0], jnp.int32)
    socket_of = _thread_nodes(n_per, n_threads)
    usage, caps = _resource_tensor(machine, read_unit, write_unit, socket_of)

    s = machine.sockets
    onehot = jax.nn.one_hot(socket_of, s)
    rr = onehot[:, :, None] * read_unit[:, None, :]
    ww = onehot[:, :, None] * write_unit[:, None, :]
    off = (1.0 - jnp.eye(s))[None, :, :]
    cross = np.asarray(rr * off + ww * off)  # (n, s, s)
    legacy = np.zeros((n_threads, topo.n_links), np.float64)
    for i in range(s):
        for j in range(s):
            for l in topo.route(i, j):
                legacy[:, l] += cross[:, i, j]
    n_links = topo.n_links
    np.testing.assert_allclose(
        np.asarray(usage[:, -n_links:]), legacy, rtol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(caps[-n_links:]), np.asarray(topo.link_bw, np.float32)
    )
    # a 2-hop pair's flow shows up on BOTH links of its route
    t = 0  # thread 0 lives on socket 0; pair (0, 5) routes over 2 links
    route = topo.route(0, 5)
    assert len(route) == 2
    for l in route:
        a, b = topo.link_ends[l]
        contributions = sum(
            cross[t, i, j]
            for i in range(s)
            for j in range(s)
            if l in topo.route(i, j)
        )
        np.testing.assert_allclose(float(usage[t, -n_links + l]), contributions, rtol=1e-5)


def test_misfit_detector_still_flags_violators_on_4socket():
    good = benchmark_workload("Swim", 16)
    bad = benchmark_workload("Page rank", 16)
    m_good = float(misfit_score(profile_pair(E7_4830_V3, good)[0], "read"))
    m_bad = float(misfit_score(profile_pair(E7_4830_V3, bad)[0], "read"))
    assert m_bad > 10 * (m_good + 1e-6)
