"""Node-graph machine model: sockets decoupled from NUMA nodes.

* **Behavior preservation**: homogeneous ``nodes_per_socket=1`` machines
  must reproduce the pre-refactor per-socket model — proven three ways:
  ``simulate_reference`` (the per-thread path) stays *bit for bit* equal
  to a verbatim replica of the pre-refactor ``simulate``
  (platform-independent) and to byte digests recorded from the
  pre-refactor code on both 2-socket paper presets (golden; re-record if
  the pinned jax/XLA version ever changes), while the group-collapsed
  ``simulate`` hot path matches the replica to <= 1e-6 (its max-min
  arithmetic reorders float sums across a group's identical rows).
* **Sub-NUMA clustering**: the SNC-2 preset (4 half-socket nodes, shared
  QPI ports) runs end to end through ``evaluate_batch`` and the advisor.
* **Heterogeneous core rates**: the throttled preset issues, demands and
  ranks according to per-node rates.
* **Placement enumeration invariants** on both machine families, plus the
  ``MachineSpec.fingerprint`` regression guard for the new fields.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.bwsig.counters import counters_from_flows
from repro.core.numa import (
    E5_2630_V3,
    E5_2630_V3_MIXED_DIMM,
    E5_2630_V3_THROTTLED,
    E5_2699_V3,
    E5_2699_V3_SNC2,
    E7_4830_V3,
    make_machine,
    mixed_workload,
    simulate,
)
from repro.core.numa.benchmarks import benchmark_workload
from repro.core.numa.evaluate import (
    count_placements,
    enumerate_placements,
    evaluate_batch,
    evaluate_suite,
    sweep_placements,
)
from repro.core.numa.simulator import (
    SimulationResult,
    _mix_rows,
    _progressive_fill,
    _resource_tensor,
    _thread_nodes,
    asymmetric_placement,
    simulate_reference,
    symmetric_placement,
)

# ---------------------------------------------------------------------------
# bit-for-bit behavior preservation for nodes_per_socket = 1
# ---------------------------------------------------------------------------


def _legacy_simulate(machine, workload, n_per_socket, **kwargs):
    """The pre-refactor per-socket ``simulate``, verbatim: scalar
    ``core_rate`` multiplications and socket-indexed everything.  Only
    valid for homogeneous machines (all node rates equal)."""
    core_rate = float(np.asarray(machine.node_rates())[0])
    elapsed = kwargs.get("elapsed", 1.0)
    noise_std = kwargs.get("noise_std", 0.0)
    background_bw = kwargs.get("background_bw", 0.0)
    key = kwargs.get("key")
    s = machine.sockets
    n = workload.n_threads
    n_per_socket = jnp.asarray(n_per_socket)
    socket_of = _thread_nodes(n_per_socket, n)

    read_mix = _mix_rows(
        workload.read_static,
        workload.read_local,
        workload.read_per_thread,
        workload.static_socket,
        socket_of,
        n_per_socket,
    )
    write_mix = _mix_rows(
        workload.write_static,
        workload.write_local,
        workload.write_per_thread,
        workload.static_socket,
        socket_of,
        n_per_socket,
    )
    read_unit = core_rate * workload.read_bpi[:, None] * read_mix
    write_unit = core_rate * workload.write_bpi[:, None] * write_mix

    usage, caps = _resource_tensor(machine, read_unit, write_unit, socket_of)
    iterations = min(usage.shape[0], usage.shape[1]) + 1
    rates = _progressive_fill(usage, caps, iterations)

    onehot = jax.nn.one_hot(socket_of, s)
    read_flows = onehot.T @ (rates[:, None] * read_unit) * elapsed
    write_flows = onehot.T @ (rates[:, None] * write_unit) * elapsed
    instructions = onehot.T @ (rates * core_rate) * elapsed

    if noise_std > 0.0 or background_bw > 0.0:
        if key is None:
            key = jax.random.PRNGKey(0)
        k1, k2, k3 = jax.random.split(key, 3)
        read_flows = read_flows * jnp.exp(
            noise_std * jax.random.normal(k1, read_flows.shape)
        ) + background_bw * elapsed / (s * s)
        write_flows = write_flows * jnp.exp(
            noise_std * jax.random.normal(k2, write_flows.shape)
        ) + background_bw * elapsed / (s * s)
        instructions = instructions * jnp.exp(
            0.2 * noise_std * jax.random.normal(k3, instructions.shape)
        )

    sample = counters_from_flows(
        read_flows, write_flows, instructions, jnp.asarray(elapsed), n_per_socket
    )
    return SimulationResult(
        rates=rates,
        read_flows=read_flows,
        write_flows=write_flows,
        sample=sample,
        throughput=rates.sum(),
    )


@pytest.mark.parametrize(
    "machine,n_per",
    [
        (E5_2630_V3, [5, 3]),
        (E5_2630_V3, [8, 0]),
        (E5_2699_V3, [12, 6]),
        (E7_4830_V3, [6, 4, 4, 2]),
    ],
)
def test_simulate_is_bitwise_legacy_for_single_node_sockets(machine, n_per):
    wl = benchmark_workload("CG", int(sum(n_per)))
    for kwargs in (
        {},
        {"noise_std": 0.02, "background_bw": 1e8, "key": jax.random.PRNGKey(9)},
    ):
        ref = simulate_reference(machine, wl, jnp.asarray(n_per, jnp.int32), **kwargs)
        old = _legacy_simulate(machine, wl, jnp.asarray(n_per, jnp.int32), **kwargs)
        for got, want in zip(jax.tree.leaves(ref), jax.tree.leaves(old)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # the grouped hot path reorders float sums across identical rows:
        # equal to the per-thread model within solver tolerance, not bits
        new = simulate(machine, wl, jnp.asarray(n_per, jnp.int32), **kwargs)
        for got, want in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
            )


def _digest(*arrays) -> str:
    d = hashlib.blake2b(digest_size=16)
    for a in arrays:
        d.update(np.asarray(a).tobytes())
    return d.hexdigest()


# Byte digests of simulate outputs recorded from the pre-refactor
# per-socket implementation (commit 43408e4) under the pinned jax version
# — CG @ 8 threads on both 2-socket paper presets.  ``simulate_reference``
# (the per-thread path) must still reproduce them byte for byte.  The
# ``batch`` digests pin the group-collapsed ``evaluate_batch`` pipeline
# instead (re-recorded at the grouped-solver PR and again at the
# shared-slab batch PR, which batched the measurement-noise draws — new
# PRNG stream, same model; equivalence with the per-thread reference is
# covered to 1e-6 by tests/test_grouped_solver.py and noise-free by
# tests/test_placement_sweep.py).  The noisy digests (``batch``,
# ``simnoise``) were re-recorded on jax 0.9.0, whose default threefry is
# the partitionable one: a new noise stream, same model.  The noise-free
# ``sim`` digests are unchanged since the pre-refactor code.  The
# ``batch`` digests were re-recorded again when the noise factor
# ``exp(noise_std * z)`` became a series exact to float32
# (``simulator.noise_factor``): the same draws and model, flows differing
# in their last bits.  They were re-recorded once more when the batched
# fill came to keep the placements on its arrays' last axis and to sum
# the frozen and active loads in one pass: the same model, a few flows
# and errors differing in their last bits.  Byte digests pin one
# jax/XLA-CPU build; a jax upgrade may re-record them.
_PRE_REFACTOR_DIGESTS = {
    ("E5-2630v3-8c", "batch"): "9fed416dea7fd3ec71eb72a7184425d5",
    ("E5-2630v3-8c", "sim"): "26bc2013541a68d19b0f83cb220ab9d4",
    ("E5-2630v3-8c", "simnoise"): "af2a8dd6886b1229eb47b454d1a97c49",
    ("E5-2699v3-18c", "batch"): "19ed8151c09fd85cdb98521aae69d94c",
    ("E5-2699v3-18c", "sim"): "d129b2fbbb31f4fe72f22f3a7e6ce368",
    ("E5-2699v3-18c", "simnoise"): "903ccf1aafd8544a4b8c650e8b7c72b3",
}


@pytest.mark.parametrize("machine", [E5_2630_V3, E5_2699_V3])
def test_golden_digests_match_pre_refactor_model(machine):
    """simulate_reference reproduces the pre-refactor outputs byte for
    byte on both 2-socket presets; the jitted grouped evaluate_batch
    pipeline reproduces its own recorded digests (change detector)."""
    wl = benchmark_workload("CG", 8)
    batch = evaluate_batch(
        machine,
        [wl],
        sweep_placements(machine, 8),
        noise_std=0.02,
        keys=jnp.stack([jax.random.PRNGKey(3)]),
    )
    assert (
        _digest(
            batch.errors_read, batch.errors_write, batch.errors_combined, batch.total_bw
        )
        == _PRE_REFACTOR_DIGESTS[(machine.name, "batch")]
    )
    res = simulate_reference(machine, wl, jnp.asarray([5, 3], jnp.int32))
    assert (
        _digest(
            res.rates,
            res.read_flows,
            res.write_flows,
            res.sample.local_read,
            res.sample.remote_read,
            res.sample.local_write,
            res.sample.remote_write,
            res.sample.instructions,
        )
        == _PRE_REFACTOR_DIGESTS[(machine.name, "sim")]
    )
    resn = simulate_reference(
        machine,
        wl,
        jnp.asarray([2, 6], jnp.int32),
        noise_std=0.02,
        background_bw=1e8,
        key=jax.random.PRNGKey(9),
    )
    assert (
        _digest(resn.rates, resn.read_flows, resn.write_flows, resn.sample.instructions)
        == _PRE_REFACTOR_DIGESTS[(machine.name, "simnoise")]
    )


# ---------------------------------------------------------------------------
# sub-NUMA clustering end to end
# ---------------------------------------------------------------------------


def test_snc2_preset_shape():
    m = E5_2699_V3_SNC2
    assert m.sockets == 2 and m.nodes_per_socket == 2
    assert m.n_nodes == 4 and m.cores_per_node == 9
    assert m.topology.n_nodes == 4
    m.validate()
    np.testing.assert_array_equal(
        np.asarray(symmetric_placement(m, 16)), [4, 4, 4, 4]
    )
    asym = np.asarray(asymmetric_placement(m, 16))
    assert asym.sum() == 16 and asym.max() <= 9 and len(set(asym.tolist())) > 1


def test_snc2_evaluate_batch_noise_free_exact():
    """In-model workloads stay exactly representable over 4 half-socket
    nodes: fit on 2 runs, predict every placement, zero error."""
    m = E5_2699_V3_SNC2
    wl = benchmark_workload("CG", 16)
    placements = enumerate_placements(m, 16, max_placements=24, seed=2)
    batch = evaluate_batch(m, wl, placements, keys=jax.random.PRNGKey(5))
    errs = np.asarray(batch.errors_combined)
    assert errs.shape == (1, 24, 2 * m.n_nodes)
    assert np.isfinite(errs).all()
    assert errs.max() < 2e-3


def test_snc2_advisor_end_to_end():
    from repro.core.meshsig.advisor import rank_numa_placements

    m = E5_2699_V3_SNC2
    wl = benchmark_workload("CG", 16)
    ranked = rank_numa_placements(m, wl, max_placements=64, top_k=8)
    assert len(ranked) == 8
    thrs = [r.predicted_throughput for r in ranked]
    assert thrs == sorted(thrs, reverse=True)
    assert all(sum(r.placement) == 16 for r in ranked)
    assert all(max(r.placement) <= m.cores_per_node for r in ranked)


def test_snc2_shared_qpi_port_caps_both_nodes():
    """Both of socket 0's nodes streaming to socket 1 share ONE QPI link:
    total cross-socket traffic stays within that link's capacity, which a
    2-endpoint-per-socket (fully connected) machine would exceed."""
    from repro.core.numa import fully_connected

    m = E5_2699_V3_SNC2._replace(
        local_read_bw=400e9,  # decap banks: isolate the interconnect
        remote_read_bw=400e9,
        hop_attenuation=1.0,
    )
    wl = mixed_workload(
        "cross", 8, read_mix=(1.0, 0.0, 0.0), read_bpi=16.0, write_bpi=0.0,
        static_socket=2,  # socket 1's endpoint node
    )
    p = jnp.asarray([4, 4, 0, 0], jnp.int32)  # all threads on socket 0
    res = simulate(m, wl, p)
    qpi_bw = dict(zip(m.topology.link_ends, m.topology.link_bw))[(0, 2)]
    cross = float(np.asarray(res.read_flows)[:2, 2:].sum())
    assert cross <= qpi_bw * (1 + 1e-4)
    # same machine with per-node direct links moves strictly more
    direct = m._replace(topology=fully_connected(4, qpi_bw))
    res_direct = simulate(direct, wl, p)
    assert float(res_direct.throughput) > float(res.throughput)


def test_snc2_evaluate_suite_default_threads():
    """evaluate_suite's default thread count rounds down to a node-even
    split (18 -> 16 on the SNC-2 preset) and the suite runs end to end."""
    r = evaluate_suite(
        E5_2699_V3_SNC2, include_violators=False, max_placements=8, noise_std=0.02
    )
    assert r.all_errors.size > 0
    assert 0.0 < r.median_error_pct < 2.34


# ---------------------------------------------------------------------------
# heterogeneous core rates end to end
# ---------------------------------------------------------------------------


def test_throttled_node_issues_fewer_instructions():
    m = E5_2630_V3_THROTTLED
    wl = mixed_workload("cpu", 4, read_mix=(0.0, 1.0, 0.0), read_bpi=1e-3)
    res = simulate(m, wl, jnp.asarray([2, 2], jnp.int32))
    instr = np.asarray(res.sample.instructions)
    # unconstrained threads run at rate 1.0: instruction ratio == rate ratio
    np.testing.assert_allclose(instr[1] / instr[0], 1.6e9 / 2.4e9, rtol=1e-5)
    # and bandwidth demand scales with the node rate too
    flows = np.asarray(res.read_flows)
    np.testing.assert_allclose(
        flows[1, 1] / flows[0, 0], 1.6e9 / 2.4e9, rtol=1e-5
    )


def test_throttled_advisor_prefers_fast_node():
    """A compute-bound workload concentrates on the fast socket: the
    roofline's per-node rate weighting beats plain thread counting."""
    from repro.core.meshsig.advisor import rank_numa_placements

    m = E5_2630_V3_THROTTLED
    wl = mixed_workload("cpu", 6, read_mix=(0.1, 0.7, 0.1), read_bpi=0.3)
    ranked = rank_numa_placements(m, wl)
    assert ranked[0].placement[0] > ranked[0].placement[1]
    # the homogeneous twin has no such preference at equal remote fractions
    best, worst = ranked[0], ranked[-1]
    assert best.predicted_throughput > worst.predicted_throughput


def test_throttled_remote_fraction_is_demand_weighted():
    """remote_fraction must follow traffic (thread count x node rate), not
    raw thread count: with a pure-Static-on-node-0 signature and an equal
    [4, 4] split on the throttled machine, node 0 carries 2.4/(2.4+1.6) =
    0.6 of the demand, so 0.4 of the traffic is remote — not 0.5."""
    from repro.core.bwsig import DirectionSignature
    from repro.core.meshsig.advisor import _placement_scores

    sig = DirectionSignature.make(static_socket=0, static_fraction=1.0)
    fracs, _ = _placement_scores(
        E5_2630_V3_THROTTLED,
        sig,
        sig,
        jnp.asarray([[4, 4]], jnp.int32),
        1.0,
        0.25,
    )
    np.testing.assert_allclose(float(fracs[0]), 1.0 - 0.6, rtol=1e-6)
    # the homogeneous twin keeps the plain thread weighting
    fracs_h, _ = _placement_scores(
        E5_2630_V3, sig, sig, jnp.asarray([[4, 4]], jnp.int32), 1.0, 0.25
    )
    np.testing.assert_allclose(float(fracs_h[0]), 0.5, rtol=1e-6)
    # sub-unit demand mass must still normalize: one thread on the slow
    # node with a fully-local signature has zero remote traffic
    local = DirectionSignature.make(local_fraction=1.0)
    fracs_1, _ = _placement_scores(
        E5_2630_V3_THROTTLED,
        local,
        local,
        jnp.asarray([[0, 1]], jnp.int32),
        1.0,
        0.25,
    )
    np.testing.assert_allclose(float(fracs_1[0]), 0.0, atol=1e-6)


def test_throttled_machine_through_evaluate_batch():
    m = E5_2630_V3_THROTTLED
    wl = benchmark_workload("Swim", 8)
    batch = evaluate_batch(m, wl, sweep_placements(m, 8), keys=jax.random.PRNGKey(1))
    errs = np.asarray(batch.errors_combined)
    assert np.isfinite(errs).all()
    assert errs.max() < 2e-3  # noise-free + in-model stays exact


# ---------------------------------------------------------------------------
# MachineSpec.fingerprint guards the signature cache
# ---------------------------------------------------------------------------


def test_fingerprint_changes_with_node_fields():
    base = E5_2630_V3_THROTTLED
    fp = base.fingerprint()
    # any per-node core-rate entry
    assert base._replace(core_rate=(2.4e9, 1.7e9)).fingerprint() != fp
    assert base._replace(core_rate=(2.3e9, 1.6e9)).fingerprint() != fp
    # tuple vs scalar spelling must not collide either
    assert (
        base._replace(core_rate=(2.4e9, 2.4e9)).fingerprint()
        != base._replace(core_rate=2.4e9).fingerprint()
    )
    # nodes_per_socket participates even with everything else fixed
    snc = E5_2699_V3_SNC2
    flat = snc._replace(nodes_per_socket=1, sockets=4, cores_per_socket=9)
    assert flat.n_nodes == snc.n_nodes  # same node count, different meaning
    assert flat.fingerprint() != snc.fingerprint()
    # and the permutation of a heterogeneous rate vector matters
    assert (
        base._replace(core_rate=(1.6e9, 2.4e9)).fingerprint() != fp
    )


def test_make_machine_validates_node_fields():
    with pytest.raises(ValueError):
        make_machine("bad", sockets=2, cores_per_socket=9, nodes_per_socket=2)
    with pytest.raises(ValueError):
        make_machine("bad", sockets=2, core_rate=(2.4e9, 2.4e9, 2.4e9))
    with pytest.raises(ValueError):
        make_machine("bad", sockets=2, nodes_per_socket=0)
    m = make_machine(
        "ok", sockets=2, cores_per_socket=8, nodes_per_socket=2,
        core_rate=(2.4e9, 2.4e9, 1.8e9, 1.8e9),
    )
    assert m.n_nodes == 4 and m.topology.name == "snc2x2"
    assert isinstance(m.core_rate, tuple)


# ---------------------------------------------------------------------------
# per-node local bandwidth vectors (mixed DIMM populations)
# ---------------------------------------------------------------------------


def test_node_local_bw_broadcasts_scalar_and_tuple():
    """Every per-node consumer of local_*_bw goes through node_local_bw:
    scalars broadcast (the pre-refactor path, same values/dtype), tuples
    map each bank to its own capacity."""
    np.testing.assert_array_equal(
        np.asarray(E5_2630_V3.node_local_bw("read")),
        np.full((2,), E5_2630_V3.local_read_bw, np.float32),
    )
    np.testing.assert_array_equal(
        np.asarray(E5_2630_V3_MIXED_DIMM.node_local_bw("read")),
        np.asarray([52e9, 26e9], np.float32),
    )
    np.testing.assert_array_equal(
        np.asarray(E5_2630_V3_MIXED_DIMM.bank_write_caps()),
        np.asarray([28e9, 14e9], np.float32),
    )
    with pytest.raises(ValueError):
        E5_2630_V3.node_local_bw("sideways")


def test_local_bw_tuple_validation_and_fingerprint():
    with pytest.raises(ValueError):
        E5_2630_V3._replace(local_read_bw=(52e9,)).validate()
    with pytest.raises(ValueError):
        E5_2630_V3._replace(local_write_bw=(28e9, -1.0)).validate()
    # tuple vs scalar spelling must not collide in signature-cache keys
    fp = E5_2630_V3.fingerprint()
    assert E5_2630_V3._replace(local_read_bw=(52e9, 52e9)).fingerprint() != fp
    assert E5_2630_V3_MIXED_DIMM.fingerprint() != fp
    # and the per-node values themselves participate
    assert (
        E5_2630_V3_MIXED_DIMM._replace(local_read_bw=(26e9, 52e9)).fingerprint()
        != E5_2630_V3_MIXED_DIMM.fingerprint()
    )


def test_mixed_dimm_banks_cap_per_node():
    """Simulation respects each bank's own capacity: the half-populated
    bank saturates at half the bandwidth of the full one."""
    m = E5_2630_V3_MIXED_DIMM
    wl = mixed_workload("local", 8, read_mix=(0.0, 1.0, 0.0), read_bpi=8.0)
    res = simulate(m, wl, jnp.asarray([4, 4], jnp.int32))
    reads = np.asarray(res.read_flows).sum(0)
    assert np.isclose(reads[0], 52e9, rtol=1e-3)
    assert np.isclose(reads[1], 26e9, rtol=1e-3)


def test_mixed_dimm_through_evaluate_batch_and_advisor():
    """The scalar/tuple coercion audit end to end: the batched fit+predict
    engine stays exact on an in-model workload, and the advisor's roofline
    charges each bank its own capacity (so a bandwidth-bound workload
    concentrates on the fat-DIMM node)."""
    from repro.core.meshsig.advisor import rank_numa_placements

    m = E5_2630_V3_MIXED_DIMM
    wl = benchmark_workload("Swim", 8)
    batch = evaluate_batch(m, wl, sweep_placements(m, 8), keys=jax.random.PRNGKey(2))
    errs = np.asarray(batch.errors_combined)
    assert np.isfinite(errs).all()
    assert errs.max() < 2e-3
    heavy = mixed_workload("bw", 6, read_mix=(0.0, 1.0, 0.0), read_bpi=8.0)
    ranked = rank_numa_placements(m, heavy)
    assert ranked[0].placement[0] > ranked[0].placement[1]


def test_make_machine_canonicalizes_local_bw_sequences():
    m = make_machine(
        "mixed", sockets=2, cores_per_socket=8,
        local_read_bw=[50e9, 25e9], local_write_bw=[28e9, 14e9],
        remote_read_ratio=0.2, remote_write_ratio=0.3,
    )
    assert m.local_read_bw == (50e9, 25e9)
    assert isinstance(m.local_read_bw, tuple)
    # remote path bases anchor on the mean bank bandwidth
    assert m.remote_read_bw == pytest.approx(0.2 * 37.5e9)
    assert m.remote_write_bw == pytest.approx(0.3 * 21e9)
    with pytest.raises(ValueError):
        make_machine("bad", sockets=2, local_read_bw=[50e9, 25e9, 10e9])


# ---------------------------------------------------------------------------
# placement-enumeration invariants (homogeneous and SNC-2)
# ---------------------------------------------------------------------------

_ENUM_MACHINES = [E5_2630_V3, E5_2699_V3_SNC2, E5_2630_V3_THROTTLED]


@pytest.mark.parametrize("machine", _ENUM_MACHINES)
@pytest.mark.parametrize("n_threads", [1, 7, 16])
def test_enumeration_invariants(machine, n_threads):
    if n_threads > machine.n_nodes * machine.cores_per_node:
        with pytest.raises(ValueError):
            enumerate_placements(machine, n_threads)
        return
    full = np.asarray(enumerate_placements(machine, n_threads))
    assert full.shape == (count_placements(machine, n_threads), machine.n_nodes)
    assert (full.sum(axis=1) == n_threads).all()
    assert full.min() >= 0 and full.max() <= machine.cores_per_node
    assert len({tuple(r) for r in full.tolist()}) == full.shape[0]

    budget = max(1, full.shape[0] // 2)
    a = np.asarray(enumerate_placements(machine, n_threads, max_placements=budget, seed=5))
    b = np.asarray(enumerate_placements(machine, n_threads, max_placements=budget, seed=5))
    np.testing.assert_array_equal(a, b)  # deterministic under the budget
    assert a.shape[0] == min(budget, full.shape[0])
    full_set = {tuple(r) for r in full.tolist()}
    assert all(tuple(r) in full_set for r in a.tolist())


@settings(max_examples=25, deadline=None)
@given(
    n_threads=st.integers(1, 24),
    sockets=st.integers(2, 4),
    cores=st.integers(2, 8),
    nodes_per_socket=st.integers(1, 2),
    seed=st.integers(0, 3),
)
def test_property_enumeration_invariants(
    n_threads, sockets, cores, nodes_per_socket, seed
):
    """enumerate_placements rows sum to n_threads, respect per-node core
    caps, match count_placements, and subsample deterministically — on
    homogeneous and sub-NUMA-clustered machines alike."""
    cores_per_socket = cores * nodes_per_socket  # always divisible
    machine = make_machine(
        "prop",
        sockets=sockets,
        cores_per_socket=cores_per_socket,
        nodes_per_socket=nodes_per_socket,
    )
    total_cores = machine.n_nodes * machine.cores_per_node
    if n_threads > total_cores:
        with pytest.raises(ValueError):
            enumerate_placements(machine, n_threads)
        return
    full = np.asarray(enumerate_placements(machine, n_threads))
    assert full.shape == (count_placements(machine, n_threads), machine.n_nodes)
    assert (full.sum(axis=1) == n_threads).all()
    assert full.min() >= 0 and full.max() <= machine.cores_per_node
    a = np.asarray(enumerate_placements(machine, n_threads, max_placements=16, seed=seed))
    b = np.asarray(enumerate_placements(machine, n_threads, max_placements=16, seed=seed))
    np.testing.assert_array_equal(a, b)
    assert a.shape[0] == min(16, full.shape[0])
