"""Properties of the max-min fair NUMA bandwidth simulator."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.numa import (
    E5_2630_V3,
    E5_2699_V3,
    mixed_workload,
    pure_workload,
    simulate,
)
from repro.core.numa.simulator import _mix_rows, _resource_tensor, _thread_nodes, noise_factor


def test_thread_node_assignment_contiguous():
    got = _thread_nodes(jnp.asarray([3, 1]), 4)
    np.testing.assert_array_equal(np.asarray(got), [0, 0, 0, 1])


def test_rates_in_unit_interval():
    wl = mixed_workload("m", 16, read_mix=(0.2, 0.3, 0.3))
    res = simulate(E5_2699_V3, wl, jnp.asarray([8, 8]))
    r = np.asarray(res.rates)
    assert (r > 0).all() and (r <= 1.0 + 1e-6).all()


def test_unconstrained_threads_run_full_speed():
    """A workload with negligible bandwidth demand is CPU-bound: x == 1."""
    wl = mixed_workload("tiny", 8, read_mix=(0.0, 1.0, 0.0), read_bpi=1e-4, write_bpi=0.0)
    res = simulate(E5_2630_V3, wl, jnp.asarray([4, 4]))
    np.testing.assert_allclose(np.asarray(res.rates), 1.0, atol=1e-5)


def test_capacity_constraints_respected():
    """No resource exceeds its capacity at the solved rates."""
    wl = mixed_workload("heavy", 16, read_mix=(0.5, 0.0, 0.0), read_bpi=4.0, write_bpi=2.0)
    machine = E5_2630_V3
    n_per = jnp.asarray([8, 8])
    res = simulate(machine, wl, n_per)
    # banks
    assert float(res.read_flows.sum(0).max()) <= machine.local_read_bw * (1 + 1e-4)
    assert float(res.write_flows.sum(0).max()) <= machine.local_write_bw * (1 + 1e-4)
    # remote paths
    off = ~np.eye(2, dtype=bool)
    assert np.asarray(res.read_flows)[off].max() <= machine.remote_read_bw * (1 + 1e-4)
    assert np.asarray(res.write_flows)[off].max() <= machine.remote_write_bw * (1 + 1e-4)
    # interconnect (2 sockets: one link carries all cross traffic)
    qpi = float(np.asarray(res.read_flows)[off].sum() + np.asarray(res.write_flows)[off].sum())
    assert qpi <= float(machine.link_caps()[0]) * (1 + 1e-4)


def test_maxmin_some_resource_saturated_or_full_speed():
    wl = mixed_workload("sat", 16, read_mix=(1.0, 0.0, 0.0), read_bpi=2.0)
    machine = E5_2630_V3
    res = simulate(machine, wl, jnp.asarray([8, 8]))
    r = np.asarray(res.rates)
    if not np.allclose(r, 1.0):
        # static reads all hit bank 0: either the bank's read capacity or
        # the remote read path into it must be tight (max-min: someone's
        # bottleneck is saturated)
        bank0 = float(res.read_flows.sum(0)[0])
        remote0 = float(res.read_flows[1, 0])
        assert np.isclose(bank0, machine.local_read_bw, rtol=1e-3) or np.isclose(
            remote0, machine.remote_read_bw, rtol=1e-3
        ), (bank0, remote0)


def test_remote_saturation_slows_threads():
    """Static memory on socket 0, threads split: remote threads are limited
    by the weak remote path on the 8-core machine (paper Figure 1)."""
    wl = pure_workload("static", 8, "static", read_bpi=1.0, static_socket=0)
    machine = E5_2630_V3
    res = simulate(machine, wl, jnp.asarray([4, 4]))
    r = np.asarray(res.rates)
    # threads 0-3 are local to the static bank, 4-7 remote
    assert r[4:].max() < r[:4].min()


def test_18core_more_forgiving_than_8core():
    """Paper Figure 1: the 18-core machine tolerates remote placement far
    better than the 8-core machine."""
    def remote_penalty(machine, n):
        wl = pure_workload("static", n, "static", read_bpi=0.9, static_socket=0)
        local = simulate(machine, wl, jnp.asarray([n, 0])).throughput
        split = simulate(machine, wl, jnp.asarray([n // 2, n // 2])).throughput
        return float(local) / float(split)

    p8 = remote_penalty(E5_2630_V3, 8)
    p18 = remote_penalty(E5_2699_V3, 18)
    # On the cheap machine remote access hurts much more.
    assert p8 > p18


def test_vmap_over_placements():
    """The §6.2.2 evaluation shape: thousands of placements in one call."""
    wl = mixed_workload("v", 16, read_mix=(0.2, 0.3, 0.3))
    placements = jnp.stack(
        [jnp.asarray([i, 16 - i], jnp.int32) for i in range(1, 16)]
    )
    f = jax.vmap(lambda p: simulate(E5_2699_V3, wl, p).throughput)
    out = np.asarray(f(placements))
    assert out.shape == (15,)
    assert (out > 0).all()


def test_conservation_flows_match_demand():
    """Total flows equal sum over threads of rate*intensity*core_rate,
    each thread issuing at its node's rate."""
    wl = mixed_workload("c", 8, read_mix=(0.1, 0.5, 0.2), read_bpi=0.4, write_bpi=0.1)
    machine = E5_2699_V3
    n_per = jnp.asarray([5, 3])
    res = simulate(machine, wl, n_per)
    rate_of = np.asarray(machine.node_rates())[np.asarray(_thread_nodes(n_per, 8))]
    expect_read = float((np.asarray(res.rates) * rate_of * np.asarray(wl.read_bpi)).sum())
    np.testing.assert_allclose(float(res.read_flows.sum()), expect_read, rtol=1e-5)


@settings(max_examples=20, deadline=None)
@given(
    n0=st.integers(1, 8),
    bpi=st.floats(0.01, 4.0),
    mix=st.lists(st.floats(0.0, 1.0), min_size=3, max_size=3).filter(
        lambda f: sum(f) <= 1.0
    ),
)
def test_property_caps_never_exceeded(n0, bpi, mix):
    wl = mixed_workload("p", 8, read_mix=tuple(mix), read_bpi=bpi, write_bpi=bpi / 3)
    machine = E5_2630_V3
    res = simulate(machine, wl, jnp.asarray([n0, 8 - n0]))
    read = np.asarray(res.read_flows)
    write = np.asarray(res.write_flows)
    assert read.sum(0).max() <= machine.local_read_bw * (1 + 1e-3)
    assert write.sum(0).max() <= machine.local_write_bw * (1 + 1e-3)
    off = ~np.eye(2, dtype=bool)
    assert read[off].max() <= machine.remote_read_bw * (1 + 1e-3)
    assert write[off].max() <= machine.remote_write_bw * (1 + 1e-3)
    assert (np.asarray(res.rates) <= 1 + 1e-6).all()


def _ulps(got: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Distance of float32 ``got`` from the float64 ``exp(x)``, in float32
    ulp at the exact value."""
    exact = np.exp(x.astype(np.float64))
    return np.abs(got.astype(np.float64) - exact) / np.spacing(exact.astype(np.float32))


def test_noise_factor_is_exp_within_4_ulp_over_its_series_range():
    x = np.concatenate([
        np.linspace(-0.25, 0.25, 200_001, dtype=np.float32),
        0.02 * np.random.default_rng(0).standard_normal(100_000).astype(np.float32),
    ])
    got = np.asarray(jax.jit(noise_factor)(jnp.asarray(x)))
    assert got.dtype == np.float32
    assert _ulps(got, x).max() <= 4.0


def test_noise_factor_falls_back_to_exp_outside_its_series_range():
    x = np.asarray([-3.0, -0.2501, 0.2501, 0.5, 2.0], np.float32)
    got = np.asarray(jax.jit(noise_factor)(jnp.asarray(x)))
    np.testing.assert_array_equal(got, np.asarray(jnp.exp(jnp.asarray(x))))
    # the series alone would be off by far more there
    series = sum(x.astype(np.float64) ** k / np.prod(np.arange(1, k + 1)) for k in range(8))
    assert np.abs(series[0] - np.exp(-3.0)) / np.exp(-3.0) > 1e-3
