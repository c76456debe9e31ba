"""Each fault a cell can have, planted under a whole benchmark run on the
CPU (the run's look for a chip skipped): ``correct`` must come out false.

The faults: an answer altered where it is produced, half of a batch
left out, and a placement table that is not the configuration's.  A step that returns its state unchanged (training) and the
exchange between chips (several chips) do not exist in these one-chip
advisor cells.
"""

import json
import time

import jax
import numpy as np
import pytest

from bench import core, run

SEED = 2**34 + 5
CELLS = ["sweep.e7-4830v3-4s.table1"]


def _run(name, capsys, root=core.ROOT, seconds=0.5):
    jax.clear_caches()  # a planted fault must be traced anew
    rc = run.main(
        ["--workload", name, "--seed", str(SEED), "--seconds", str(seconds), "--trace", "0"],
        require_accelerator=False, t_start=time.perf_counter(), root=root,
    )
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    jax.clear_caches()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_sweep_run_is_correct(cell, capsys):
    result = _run(cell, capsys)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_sweep_flow_altered_in_the_fill(cell, capsys, monkeypatch):
    """The fill's flows scaled by 1 + 1e-4 where the simulator produces
    them: every run bandwidth moves by that much."""
    import repro.core.numa.evaluate as ev

    real = ev.simulate_grouped_batch

    def altered(*args, **kwargs):
        out = real(*args, **kwargs)
        return out._replace(read_flows=out.read_flows * (1.0 + 1e-4))

    monkeypatch.setattr(ev, "simulate_grouped_batch", altered)
    result = _run(cell, capsys)
    assert not result["correct"]
    assert result["checks"]["bw_dev"]["value"] > result["checks"]["bw_dev"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_sweep_half_the_placements_left_out(cell, capsys, monkeypatch):
    """Only the first half of the placement table is evaluated; its rows
    stand in for the rest."""
    import repro.core.numa.evaluate as ev

    real = ev.evaluate_batch

    def half(machine, workloads, placements, **kwargs):
        p = np.asarray(placements)
        h = p.shape[0] // 2
        out = real(machine, workloads, p[:h], **kwargs)
        fill = lambda x: jax.numpy.concatenate([x, x], axis=1)[:, : p.shape[0]]  # noqa: E731
        return out._replace(
            placements=placements,
            total_bw=fill(out.total_bw),
            errors_read=fill(out.errors_read),
            errors_write=fill(out.errors_write),
            errors_combined=fill(out.errors_combined),
        )

    monkeypatch.setattr(ev, "evaluate_batch", half)
    result = _run(cell, capsys)
    assert not result["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_sweep_placement_table_altered(cell, capsys, monkeypatch):
    """The program's table has its last row repeated over the first: every
    row is evaluated as given, but the table is not the configuration's."""
    import repro.core.numa.evaluate as ev

    real = ev.sweep_placements

    def altered(*args, **kwargs):
        p = np.asarray(real(*args, **kwargs)).copy()
        p[0] = p[-1]
        return jax.numpy.asarray(p)

    monkeypatch.setattr(ev, "sweep_placements", altered)
    result = _run(cell, capsys)
    assert not result["correct"]
    assert result["checks"]["table_rows"]["value"] > result["checks"]["table_rows"]["limit"]
