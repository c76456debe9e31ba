"""The sweep rate, the seed handling and the reference's own placement
table."""

import numpy as np
import pytest

from bench import core, sut
from bench.drivers import sweep
from bench.reference import numa as ref

CONFIGS = ("e7-4830v3-4s",)


def test_sweep_rate_is_all_work_over_all_the_time():
    cell = core.load_cell("sweep.e7-4830v3-4s.table1")
    d = sweep.Driver(cell, 1, core.Spans(False))
    d.n_placements, d.n_workloads = 1469, 23
    d.calls = 10
    d.t_window = (100.0, 102.5)
    assert d.end_to_end()["sweep_placement_evals_per_s"] == pytest.approx(10 * 1469 * 23 / 2.5)


def test_seeds_beyond_32_bits_stay_distinct():
    big = 2**33 + 5
    assert sut.seed_words(big) == (2, 5)
    k1 = sut.call_keys(big, 0, 23)
    assert k1.shape == (23, 2) and k1.dtype == np.uint32
    assert not np.array_equal(k1, sut.call_keys(5, 0, 23))
    assert not np.array_equal(k1, sut.call_keys(big, 1, 23))
    assert not np.array_equal(k1, sut.call_keys(big, 0, 23, warmup=True))


@pytest.mark.parametrize("name", CONFIGS)
def test_enumeration_matches_the_configured_space(name):
    from repro.core.numa.evaluate import sweep_placements

    cfg = core.load_json(core.BENCH / "configs" / f"{name}.json")
    m = cfg["machine"]
    p = ref.placement_space(m["sockets"], m["cores_per_socket"], cfg["n_threads"])
    assert len(p) == cfg["placements"]["total"]
    assert ref.space_size(m["sockets"], m["cores_per_socket"], cfg["n_threads"]) == len(p)
    assert np.all(p.sum(axis=1) == cfg["n_threads"])
    assert len({tuple(r) for r in p}) == len(p)
    program = np.asarray(sweep_placements(sut.machine_spec(cfg), cfg["n_threads"]))
    table, wrong = ref.placement_table(cfg, program)
    assert wrong == 0 and np.array_equal(table, p)


def test_space_size_of_a_space_too_large_to_list():
    assert ref.space_size(8, 16, 32) == 14_016_585


def test_placement_table_counts_each_row_that_is_not_the_references():
    cfg = core.load_json(core.BENCH / "configs" / "e7-4830v3-4s.json")
    m = cfg["machine"]
    p = ref.placement_space(m["sockets"], m["cores_per_socket"], cfg["n_threads"])
    assert ref.placement_table(cfg, p[::-1])[1] == len(p) - 1  # the middle row stays
    assert ref.placement_table(cfg, p[:-3])[1] == 3
    dup = p.copy()
    dup[5] = dup[4]
    assert ref.placement_table(cfg, dup)[1] == 1
    sampled = dict(cfg, placements=dict(cfg["placements"], max_placements=8))
    rows, wrong = ref.placement_table(sampled, p[100:108])
    assert wrong == 0 and np.array_equal(rows, p[100:108])
    bad = p[100:108].copy()
    bad[0, 0] += 1  # 25 threads
    bad[1] = bad[2]
    assert ref.placement_table(sampled, bad)[1] == 2
    assert ref.placement_table(sampled, p[100:107])[1] == 1
