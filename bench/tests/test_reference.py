"""The plain reference against the program on the CPU at small sizes, and
the lower-precision control caught by the cells' limits."""

import numpy as np
import pytest

from bench import core, sut
from bench.reference import numa as ref

SEED = 2**33 + 17
CELLS = ["sweep.e7-4830v3-4s.table1"]


def _cell(name, root=core.ROOT):
    return core.load_cell(name, root)


@pytest.fixture(scope="module", params=CELLS)
def sweep_call(request):
    """One ``evaluate_batch`` call of a sweep cell, as the window makes
    it, and its rows on the host."""
    import jax.numpy as jnp

    from bench.drivers import sweep as drv
    from repro.core.numa.evaluate import evaluate_batch, sweep_placements

    cell = _cell(request.param)
    machine = sut.machine_spec(cell.config)
    n = cell.config["n_threads"]
    wls = sut.workloads(cell.traffic, n)
    placements = sweep_placements(machine, n)
    out = evaluate_batch(machine, wls, placements, noise_std=cell.traffic["noise_std"],
                         keys=jnp.asarray(sut.call_keys(SEED, 0, len(wls))))
    return cell, drv, [(0, drv.to_host(out))], np.asarray(placements)


@pytest.mark.parametrize("cell_name", CELLS)
def test_objective_matches_exact_objectives(cell_name):
    from repro.core.numa.search import exact_objectives

    cell = _cell(cell_name)
    n = cell.config["n_threads"]
    machine = ref.machine_from_config(cell.config["machine"])
    spec = sut.machine_spec(cell.config)
    space = ref.placement_space(machine.s, machine.cap, n)
    p = space[np.random.default_rng(0).choice(len(space), size=min(16, len(space)), replace=False)]
    for w in cell.traffic["workloads"][:4] + cell.traffic["workloads"][-1:]:
        wl_prog = sut.workloads({"workloads": [w]}, n)[0]
        got = exact_objectives(spec, wl_prog, p)
        want, _ = ref.objective(machine, ref.workload_arrays(w, n), p)
        np.testing.assert_allclose(got, want, rtol=2e-6)


def test_reference_rows_match_evaluate_batch(sweep_call):
    cell, drv, kept, placements = sweep_call
    got = drv.compare(cell, SEED, kept, placements, sample=128)
    # the program computes in float32 on the CPU: far inside the limits
    assert got["bw_rel"] < 2e-6 and got["err_abs"] < 2e-6, got


def test_sweep_control_is_caught(sweep_call):
    cell, drv, kept, placements = sweep_call
    lim = cell.limits["limits"]
    program = drv.compare(cell, SEED, kept, placements, sample=128)
    control = drv.compare(cell, SEED, kept, placements, sample=128, ar=ref.CONTROL)
    assert all(program[k] <= lim[k] for k in lim), (program, lim)
    assert any(control[k] > lim[k] for k in lim), (control, lim)


def test_bf16x3_is_coarser_than_float32():
    rng = np.random.default_rng(1)
    a = rng.random((64, 64)) * 1e9
    b = rng.random((64,))
    exact = a @ b
    f32 = (a.astype(np.float32) @ b.astype(np.float32)).astype(float)
    high = ref.CONTROL.dot("ij,j->i", a, b).astype(float)
    assert np.max(np.abs(high - exact) / exact) > 10 * np.max(np.abs(f32 - exact) / exact)
