"""The dual-socket AMD EPYC 7601 deployment (``epyc7601-2s``): its link
graph and routes as the configuration states them, the program's and the
reference's reading of it, and its cell's limits on the CPU at a small
sample."""

import copy
import dataclasses

import numpy as np
import pytest

from bench import core, sut
from bench.reference import numa as ref

CELL = "sweep.epyc7601-2s.table1"
SEED = 2**33 + 29


@pytest.fixture(scope="module")
def cell():
    return core.load_cell(CELL)


def _socket(node: int) -> int:
    return node // 4


def test_sixteen_links_of_two_capacities(cell):
    links = cell.config["machine"]["links"]
    assert len(links) == 16
    package = [bw for a, b, bw in links if _socket(a) == _socket(b)]
    across = [(a, b, bw) for a, b, bw in links if _socket(a) != _socket(b)]
    assert len(package) == 12 and len(set(package)) == 1
    assert sorted((a, b) for a, b, _ in across) == [(i, i + 4) for i in range(4)]
    assert len({bw for *_, bw in across}) == 1 and across[0][2] != package[0]


def test_twenty_four_two_hop_routes_each_cross_one_socket_link(cell):
    m = cell.config["machine"]
    paths = m["multi_hop_paths"]
    assert len(paths) == 24
    pairs = {(p[0], p[-1]) for p in paths}
    assert pairs == {
        (i, j) for i in range(8) for j in range(8)
        if _socket(i) != _socket(j) and i % 4 != j % 4
    }
    links = {frozenset((a, b)) for a, b, _ in m["links"]}
    for p in paths:
        hops = [frozenset(e) for e in zip(p[:-1], p[1:])]
        assert len(hops) == 2 and all(h in links for h in hops)
        assert sum(_socket(a) != _socket(b) for a, b in zip(p[:-1], p[1:])) == 1


def test_placement_space_size(cell):
    from repro.core.numa.evaluate import count_placements

    m = ref.machine_from_config(cell.config["machine"])
    assert (m.s, m.cap) == (8, 8)
    n = cell.config["n_threads"]
    assert ref.space_size(m.s, m.cap, n) == 2_306_025 == cell.config["placements"]["total"]
    assert count_placements(sut.machine_spec(cell.config), n) == 2_306_025


def test_program_and_reference_read_the_same_routes_and_caps(cell):
    from repro.core.numa.simulator import machine_caps

    spec = sut.machine_spec(cell.config)
    m = ref.machine_from_config(cell.config["machine"])
    assert spec.n_nodes == m.s and spec.cores_per_node == m.cap
    np.testing.assert_array_equal(np.asarray(spec.topology.route_incidence()), m.incidence)
    np.testing.assert_allclose(np.asarray(machine_caps(spec), np.float64), m.caps, rtol=1e-7)
    hops = spec.topology.hop_matrix()
    assert (hops == 2).sum() == 24 and hops.max() == 2


def test_small_sample_is_under_the_limits_and_the_control_is_not(cell):
    """128 sampled placements through ``evaluate_batch`` on the CPU: the
    program is under every limit of the cell, the lower-precision control
    over at least one."""
    import jax.numpy as jnp

    from bench.drivers import sweep as drv
    from repro.core.numa.evaluate import evaluate_batch, sweep_placements

    config = copy.deepcopy(cell.config)
    config["placements"]["max_placements"] = 128
    small = dataclasses.replace(cell, config=config)
    spec = sut.machine_spec(config)
    n = config["n_threads"]
    wls = sut.workloads(cell.traffic, n)
    placements = sweep_placements(spec, n, max_placements=128, seed=config["placements"]["sample_seed"])
    out = evaluate_batch(spec, wls, placements, noise_std=cell.traffic["noise_std"],
                         keys=jnp.asarray(sut.call_keys(SEED, 0, len(wls))))
    kept = [(0, drv.to_host(out))]
    rows = np.asarray(placements)
    lim = cell.limits["limits"]
    program = drv.compare(small, SEED, kept, rows, sample=128)
    control = drv.compare(small, SEED, kept, rows, sample=128, ar=ref.CONTROL)
    assert program["table_rows"] == 0
    assert all(program[k] <= lim[k] for k in lim), (program, lim)
    assert any(control[k] > lim[k] for k in lim), (control, lim)
