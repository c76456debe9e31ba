"""Group-collapsed saturation solver vs the per-thread reference.

The grouped ``simulate`` hot path collapses (node, rate, bytes/instr)
equivalence classes of threads into weighted rows; these tests pin its
exact equivalence (<= 1e-6) with ``simulate_reference`` — rates, flows
and counters — across every preset, the benchmark suite (violators
included), random placements and noise keys, plus the static class
machinery itself (partition inference, multiplicities, jit/vmap paths
and differentiability through ``caps``).
"""

import re
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.numa import (
    E5_2630_V3,
    E5_2630_V3_MIXED_DIMM,
    E5_2630_V3_THROTTLED,
    E5_2699_V3,
    E5_2699_V3_SNC2,
    E7_4830_V3,
    E7_8860_V3,
    machine_caps,
    make_machine,
    mixed_workload,
    simulate,
    simulate_reference,
    simulator,
    thread_class_starts,
)
from repro.core.numa.benchmarks import benchmark_workload
from repro.core.numa.evaluate import sweep_placements
from repro.core.numa.search import _objective_batch_jit
from repro.core.numa.simulator import (
    _group_multiplicities,
    _group_resource_tensor,
    _mix_rows,
    _resource_tensor,
    _thread_nodes,
    class_starts_from_arrays,
    simulate_grouped_batch,
    support_patterns,
)
from repro.core.numa.workload import Workload, violator_workload
from test_numa_evaluate import _two_socket_eight_node_host

ALL_PRESETS = [
    E5_2630_V3,
    E5_2699_V3,
    E7_4830_V3,
    E7_8860_V3,
    E5_2699_V3_SNC2,
    E5_2630_V3_THROTTLED,
    E5_2630_V3_MIXED_DIMM,
]

RATE_TOL = 1e-6  # the tentpole's acceptance bound on |grouped - per-thread|


def _random_placement(machine, n_threads, rng):
    """A random feasible composition of n_threads over the machine's nodes."""
    s, cap = machine.n_nodes, machine.cores_per_node
    counts = np.zeros((s,), np.int64)
    for _ in range(n_threads):
        open_nodes = np.flatnonzero(counts < cap)
        counts[rng.choice(open_nodes)] += 1
    return jnp.asarray(counts, jnp.int32)


def _assert_equivalent(machine, wl, placement, **kwargs):
    a = simulate(machine, wl, placement, **kwargs)
    b = simulate_reference(machine, wl, placement, **kwargs)
    np.testing.assert_allclose(
        np.asarray(a.rates), np.asarray(b.rates), rtol=0, atol=RATE_TOL
    )
    for ga, gb in (
        (a.read_flows, b.read_flows),
        (a.write_flows, b.write_flows),
    ):
        np.testing.assert_allclose(
            np.asarray(ga), np.asarray(gb), rtol=1e-5, atol=1e-4
        )
    for ga, gb in zip(jax.tree.leaves(a.sample), jax.tree.leaves(b.sample)):
        np.testing.assert_allclose(
            np.asarray(ga), np.asarray(gb), rtol=1e-5, atol=1e-4
        )
    np.testing.assert_allclose(
        float(a.throughput), float(b.throughput), rtol=1e-5
    )


# ---------------------------------------------------------------------------
# equivalence on every preset (the acceptance bound)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("machine", ALL_PRESETS, ids=lambda m: m.name)
@pytest.mark.parametrize("bench", ["CG", "Swim", "EP", "Page rank"])
def test_grouped_matches_reference_on_all_presets(machine, bench):
    # crc32, not hash(): Python salts str hashes per process, which drew
    # different placements in every run
    rng = np.random.default_rng(zlib.crc32(f"{machine.name}/{bench}".encode()))
    n = 2 * machine.cores_per_node
    n -= n % machine.n_nodes
    wl = benchmark_workload(bench, n)
    for trial in range(3):
        placement = _random_placement(machine, n, rng)
        _assert_equivalent(machine, wl, placement)


@pytest.mark.parametrize("machine", ALL_PRESETS, ids=lambda m: m.name)
def test_grouped_matches_reference_with_noise_and_background(machine):
    """Noise multiplies the solved flows, so equal solver outputs under
    the same key must stay equal through the noisy counter path."""
    n = machine.n_nodes * 2
    wl = benchmark_workload("NPO", n)
    placement = _random_placement(machine, n, np.random.default_rng(0))
    _assert_equivalent(
        machine, wl, placement,
        noise_std=0.02, background_bw=1e8, key=jax.random.PRNGKey(17),
    )


def test_grouped_matches_reference_under_jit_and_vmap():
    """The batch engine's exact shape: traced placements, static classes."""
    machine = E7_8860_V3
    wl = benchmark_workload("Page rank", 32)
    classes = thread_class_starts(wl)
    rng = np.random.default_rng(3)
    placements = jnp.stack([_random_placement(machine, 32, rng) for _ in range(8)])

    grouped = jax.jit(
        jax.vmap(
            lambda p: simulate(machine, wl, p, thread_classes=classes).rates
        )
    )(placements)
    reference = jax.jit(
        jax.vmap(lambda p: simulate_reference(machine, wl, p).rates)
    )(placements)
    np.testing.assert_allclose(
        np.asarray(grouped), np.asarray(reference), rtol=0, atol=RATE_TOL
    )


def test_grouped_differentiable_through_caps():
    """The calibration hook: gradients of a loss through simulate(...,
    caps=...) must flow and agree with the per-thread reference."""
    machine = E5_2699_V3_SNC2
    wl = mixed_workload(  # heavy enough that banks/links actually bind
        "heavy", 16, read_mix=(0.4, 0.2, 0.2), read_bpi=8.0, write_bpi=4.0
    )
    placement = jnp.asarray([5, 3, 4, 4], jnp.int32)
    caps0 = machine_caps(machine)
    classes = thread_class_starts(wl)

    def loss_grouped(caps):
        res = simulate(machine, wl, placement, caps=caps, thread_classes=classes)
        return (res.read_flows.sum() + res.write_flows.sum()) / 1e9

    def loss_reference(caps):
        res = simulate_reference(machine, wl, placement, caps=caps)
        return (res.read_flows.sum() + res.write_flows.sum()) / 1e9

    ga = jax.grad(loss_grouped)(caps0)
    gb = jax.grad(loss_reference)(caps0)
    assert np.isfinite(np.asarray(ga)).all()
    assert float(jnp.abs(ga).max()) > 0.0  # some capacity binds
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb), rtol=1e-3, atol=1e-6)


# ---------------------------------------------------------------------------
# the static class machinery
# ---------------------------------------------------------------------------


def test_thread_class_starts_homogeneous_and_violator():
    assert thread_class_starts(mixed_workload("m", 16, read_mix=(0.2, 0.3, 0.1))) == (0,)
    assert thread_class_starts(benchmark_workload("Page rank", 16)) == (0, 8)
    # a batch shares the common refinement (union of boundaries)
    both = thread_class_starts(
        [mixed_workload("m", 16, read_mix=(0.2, 0.3, 0.1)),
         benchmark_workload("Page rank", 16)]
    )
    assert both == (0, 8)


def test_class_starts_from_arrays_runs_not_values():
    # equal values in non-adjacent runs stay separate classes (runs keep
    # the interval-overlap multiplicity computation valid)
    starts = class_starts_from_arrays([np.asarray([1.0, 2.0, 1.0, 1.0])])
    assert starts == (0, 1, 2)
    # scalars and single-thread arrays contribute no boundaries
    assert class_starts_from_arrays([np.asarray(3), np.asarray([5.0])]) == (0,)


def test_group_multiplicities_interval_overlap():
    # classes (0..3), (4..9); nodes of sizes [2, 5, 3]
    mult = np.asarray(
        _group_multiplicities((0, 4), 10, jnp.asarray([2, 5, 3], jnp.int32))
    )
    np.testing.assert_array_equal(mult, [[2, 2, 0], [0, 3, 3]])
    assert mult.sum() == 10


def test_simulate_rejects_invalid_thread_classes():
    wl = mixed_workload("m", 8, read_mix=(0.2, 0.3, 0.1))
    for bad in ((1, 4), (0, 4, 4), (0, 8)):
        with pytest.raises(ValueError):
            simulate(E5_2630_V3, wl, jnp.asarray([4, 4]), thread_classes=bad)


def test_group_resource_tensor_matches_per_thread_rows():
    """A group's unit usage row must equal the per-thread row of any of
    its members — same slab order, same remote/link charges."""
    machine = E7_8860_V3
    s = machine.n_nodes
    n = 16
    wl = benchmark_workload("CG", n)
    placement = jnp.asarray([4, 4, 2, 2, 2, 1, 1, 0], jnp.int32)
    node_of = _thread_nodes(placement, n)
    rate_of = machine.node_rates()[node_of]

    read_mix = _mix_rows(
        wl.read_static, wl.read_local, wl.read_per_thread,
        wl.static_socket, node_of, placement,
    )
    write_mix = _mix_rows(
        wl.write_static, wl.write_local, wl.write_per_thread,
        wl.static_socket, node_of, placement,
    )
    read_unit = rate_of[:, None] * wl.read_bpi[:, None] * read_mix
    write_unit = rate_of[:, None] * wl.write_bpi[:, None] * write_mix
    per_thread, caps_t = _resource_tensor(machine, read_unit, write_unit, node_of)

    res = simulate(machine, wl, placement)  # smoke: grouped path runs
    assert res.rates.shape == (n,)

    # grouped slab: CG is homogeneous -> one class, rows = nodes
    from repro.core.numa.simulator import _group_mix_rows

    g_read_mix = _group_mix_rows(
        wl.read_static[:1], wl.read_local[:1], wl.read_per_thread[:1],
        wl.static_socket, placement,
    )
    g_write_mix = _group_mix_rows(
        wl.write_static[:1], wl.write_local[:1], wl.write_per_thread[:1],
        wl.static_socket, placement,
    )
    g_read_unit = machine.node_rates()[None, :, None] * wl.read_bpi[0] * g_read_mix
    g_write_unit = machine.node_rates()[None, :, None] * wl.write_bpi[0] * g_write_mix
    grouped, caps_g = _group_resource_tensor(machine, g_read_unit, g_write_unit)
    np.testing.assert_array_equal(np.asarray(caps_t), np.asarray(caps_g))
    for t in range(n):
        k = int(node_of[t])
        np.testing.assert_allclose(
            np.asarray(grouped[k]), np.asarray(per_thread[t]), rtol=1e-6
        )


def test_violator_classes_get_distinct_rates():
    """The Page-rank violator's hot half must be able to saturate at a
    different rate than the cold half on the same node — grouping by
    (class, node) keeps that degree of freedom."""
    wl = violator_workload("pr", 8, read_bpi=6.0, hot_intensity=3.0)
    res = simulate(E5_2630_V3, wl, jnp.asarray([4, 4], jnp.int32))
    ref = simulate_reference(E5_2630_V3, wl, jnp.asarray([4, 4], jnp.int32))
    np.testing.assert_allclose(
        np.asarray(res.rates), np.asarray(ref.rates), rtol=0, atol=RATE_TOL
    )
    r = np.asarray(res.rates)
    assert not np.allclose(r[:4], r[4:])  # hot vs cold actually differ


# ---------------------------------------------------------------------------
# property sweep: random machines, workloads, placements, noise keys
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    preset=st.integers(0, len(ALL_PRESETS) - 1),
    n_threads=st.integers(1, 16),
    noise=st.sampled_from([0.0, 0.02]),
    key=st.integers(0, 2**16),
    hot=st.floats(0.0, 1.0),
    seed=st.integers(0, 2**16),
)
def test_property_grouped_equals_reference(
    preset, n_threads, noise, key, hot, seed
):
    machine = ALL_PRESETS[preset]
    n_threads = min(n_threads, machine.n_nodes * machine.cores_per_node)
    rng = np.random.default_rng(seed)
    wl = violator_workload(
        "prop", n_threads,
        hot_fraction=hot,
        hot_intensity=1.0 + 2.0 * hot,
        static_socket=int(rng.integers(machine.n_nodes)),
    )
    placement = _random_placement(machine, n_threads, rng)
    _assert_equivalent(
        machine, wl, placement,
        noise_std=noise, key=jax.random.PRNGKey(key),
    )


# ---------------------------------------------------------------------------
# the batched fill: the placements on the lanes, one loop for the batch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "host, n_threads", [("routed-8-node", 8), ("E7-4830v3", 24)]
)
def test_batch_fill_equals_each_placement_filled_alone(host, n_threads, monkeypatch):
    """The batch's loop runs until its slowest placement froze; the trips
    a placement takes after its own groups froze change nothing.  So each
    placement's rates and trip count equal, bit for bit, those of the fill
    run over its own slab alone (a trailing placement axis of 1), and all
    its outputs those of the same program filling only that placement
    (every lane a copy of it).

    The fill alone is given the batch's slab: XLA's CPU backend fuses a
    one-placement slab build otherwise, and may round its sums in another
    order."""
    machine = _two_socket_eight_node_host() if host == "routed-8-node" else E7_4830_V3
    wl = benchmark_workload("Page rank", n_threads)
    classes = thread_class_starts(wl)
    placements = np.asarray(
        sweep_placements(machine, n_threads, max_placements=48, seed=5)
    )
    support, slab_id = support_patterns(placements)
    fill = simulator._progressive_fill_structured
    operands, iterations = [], []

    def recording_fill(*args, **kwargs):  # keeps the batch fill's operands
        iterations.append(args[7])
        jax.debug.callback(lambda *a: operands.append(a), *args[:7])
        return fill(*args, **kwargs)

    monkeypatch.setattr(simulator, "_progressive_fill_structured", recording_fill)
    # the workload traced, as evaluate_batch passes it
    run = jax.jit(
        lambda arrays, p, sid: simulate_grouped_batch(
            machine, Workload("w", *arrays), p, thread_classes=classes,
            support=support, slab_id=sid,
        )
    )
    arrays = tuple(wl[1:])
    batch = run(arrays, placements, slab_id)
    jax.effects_barrier()
    slab, caps = operands[0][:4], operands[0][4:]
    fill_alone = jax.jit(
        lambda slab, caps: fill(*slab, *caps, iterations[0], early_exit=True)
    )
    rates, _ = fill_alone(slab, caps)  # the batch's own fill, replayed
    np.testing.assert_array_equal(
        np.moveaxis(np.asarray(rates), -1, 0), np.asarray(batch.group_rates)
    )
    trips = np.asarray(batch.fill_trips)
    assert len(set(trips.tolist())) > 1  # the lanes freeze at different trips
    P = len(placements)
    for i in range(P):
        copies = run(arrays, np.repeat(placements[i : i + 1], P, 0), np.repeat(slab_id[i], P))
        for name, got, want in zip(batch._fields, batch, copies):
            np.testing.assert_array_equal(
                np.asarray(got)[i], np.asarray(want)[0], err_msg=name
            )
        rates, alone_trips = fill_alone(
            tuple(np.asarray(a)[..., i : i + 1] for a in slab), caps
        )
        np.testing.assert_array_equal(
            np.asarray(batch.group_rates)[i], np.asarray(rates)[..., 0]
        )
        one = run(arrays, placements[i : i + 1], slab_id[i : i + 1])
        assert (
            trips[i] == int(alone_trips[0]) == int(one.fill_trips[0])
            == int(copies.fill_trips.max())
        )


def test_batch_cost_grows_with_the_placements_alone():
    """Search scores its leaf batches with one bucket per placement
    (``slab_id = arange(P)``).  The compiled program's operations and
    temp then grow in proportion to ``P``: each placement's bucket is
    picked at a cost that does not grow with the number of buckets."""
    wl = benchmark_workload("Page rank", 24)
    classes = thread_class_starts(wl)

    def cost(P):
        compiled = _objective_batch_jit.lower(
            E7_4830_V3, tuple(wl[1:]),
            jax.ShapeDtypeStruct((P, E7_4830_V3.n_nodes), jnp.int32), classes,
        ).compile()
        return (
            compiled.cost_analysis()["flops"],
            compiled.memory_analysis().temp_size_in_bytes,
        )

    (flops, temp), (flops_4x, temp_4x) = cost(512), cost(2048)
    assert flops_4x < 4.5 * flops  # a (P, P) pick would give ~16x
    assert temp_4x < 4.5 * temp


def test_batch_fill_keeps_the_placements_on_the_last_axis():
    """In the lowered sweep the fill's loop carries every per-placement
    operand with the placement axis last, and its condition reduces the
    whole batch's freeze mask to one 0-d predicate: no per-placement
    predicate, so no per-placement select of the loop state.  This pins
    the logical shapes; the TPU layout they lead to is bounded by
    ``test_chip_compile.py``'s compiled temp of each sweep cell."""
    machine, n_threads, P = E7_4830_V3, 24, 13  # 13: no other axis has it
    wl = benchmark_workload("Page rank", n_threads)
    placements = np.asarray(
        sweep_placements(machine, n_threads, max_placements=P, seed=5)
    )
    support, slab_id = support_patterns(placements)
    lowered = jax.jit(
        lambda arrays, p, sid: simulate_grouped_batch(
            machine, Workload("w", *arrays), p,
            thread_classes=thread_class_starts(wl), support=support, slab_id=sid,
        )
    ).lower(tuple(wl[1:]), placements, slab_id)
    text = lowered.as_text()
    ((carried, cond),) = re.findall(
        r"stablehlo\.while\([^\n]*\) : ([^\n]*)\n\s*cond \{\n(.*?)\n\s*\} do", text, re.S
    )
    shapes = [t.split("x")[:-1] for t in re.findall(r"tensor<([^>]*)>", carried)]
    lane_shapes = [d for d in shapes if str(P) in d]
    assert len(lane_shapes) >= 5  # rates, freeze mask, trips, the slab blocks
    assert all(d[-1] == str(P) for d in lane_shapes), carried
    assert re.search(rf"tensor<(\d+x)*{P}xi1>, tensor<i1>\) -> tensor<i1>", cond), cond
    assert not re.search(rf"tensor<{P}xi1>", cond), cond
