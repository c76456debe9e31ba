"""Bring-up check: drive the NUMA engine's main path once on one TPU chip.

    python chip_smoke.py

Runs in ONE process on the TPU (no child process touches the chip) and
exits non-zero at the first failed check.  Phases, in order:

1. device  - the default backend must be a TPU; there is no CPU fallback.
2. sweeps  - ``evaluate_batch`` over the 23-workload Table 1 suite on the
   4-socket (all 1469 placements), glued 8-socket (512-placement budget)
   and SNC-2 (633 placements) presets, repeated on the host CPU in this
   process; the chip's per-placement bandwidth, counter errors and error
   percentiles must agree with the CPU's.
3. grouped - ``simulate`` against the per-thread ``simulate_reference``,
   both on the chip, on every preset x {CG, Swim, EP, Page rank}.
4. search  - ``optimize_placement`` + ``branch_and_bound(gap=0.01)`` on
   the 16-node SNC-2 8-socket machine must certify the CPU's answer.
5. calibration - ``fit_machine`` recovers every SNC-2 link within 5%.
6. service - ``AdvisorService`` answers a concurrent miss stream, its
   repeats (cache), a search-tier query and a phased query, all exact,
   with zero retraces after warmup.

The last line of standard output is one JSON object naming the device;
every other line comes before it.  Times printed are set-up (first call,
compilation included) and one-run wall times on this machine, not
benchmark metrics.
"""

from __future__ import annotations

import json
import sys
import threading
import time
import zlib
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

RATE_TOL = 1e-6  # grouped vs per-thread rates (tests/test_grouped_solver.py)
SWEEP_BW_RTOL = 1e-5  # chip vs CPU per-placement total bandwidth
SWEEP_ERR_ATOL = 1e-6  # chip vs CPU counter error, as a fraction
SWEEP_PCT_ATOL = 0.01  # chip vs CPU median / p95 error, percentage points
LINK_ERR_MAX = 0.05  # calibration gate (benchmarks/calibration_roundtrip.py)
OBJ_RTOL = 1e-6  # chip vs CPU objective of one placement (f32 rounding)


def _log(msg: str) -> None:
    print(msg, flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def _check_on(tree, device, what: str) -> None:
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and leaf.devices() != {device}:
            raise AssertionError(
                f"{what}: output on {leaf.devices()}, expected {device}"
            )


def _random_placement(machine, n_threads, rng):
    """A random feasible composition (the grouped-solver tests' sampler)."""
    counts = np.zeros((machine.n_nodes,), np.int64)
    for _ in range(n_threads):
        open_nodes = np.flatnonzero(counts < machine.cores_per_node)
        counts[rng.choice(open_nodes)] += 1
    return counts.astype(np.int32)


def phase_sweeps(chip, cpu) -> None:
    from repro.core.numa import E5_2699_V3_SNC2, E7_4830_V3, E7_8860_V3
    from repro.core.numa.benchmarks import benchmark_workload, suite_names
    from repro.core.numa.evaluate import evaluate_batch, sweep_placements

    def sweep(machine, n_threads, max_placements):
        placements = sweep_placements(
            machine, n_threads, max_placements=max_placements
        )
        workloads = [benchmark_workload(b, n_threads) for b in suite_names()]
        keys = jnp.stack(
            [jax.random.fold_in(jax.random.PRNGKey(0), i)
             for i in range(len(workloads))]
        )

        def run():
            return evaluate_batch(
                machine, workloads, placements, noise_std=0.02, keys=keys
            )

        _, setup_s = _timed(run)
        batch, run_s = _timed(run)
        return batch, setup_s, run_s

    for label, machine, n_threads, budget, expect in (
        ("4-socket E7_4830_V3", E7_4830_V3, 24, None, 1469),
        ("glued 8-socket E7_8860_V3", E7_8860_V3, 32, 512, 512),
        ("SNC-2 E5_2699_V3_SNC2", E5_2699_V3_SNC2, 16, None, 633),
    ):
        on_chip, setup_s, run_s = sweep(machine, n_threads, budget)
        with jax.default_device(cpu):
            on_cpu, cpu_setup_s, cpu_run_s = sweep(machine, n_threads, budget)
        _check_on(on_chip[1:6], chip, label)
        _check_on(on_cpu[1:6], cpu, f"{label} (CPU reference)")
        n_w, n_p = on_chip.total_bw.shape
        assert (n_w, n_p) == (23, expect), (label, n_w, n_p)

        bw_t, bw_c = (np.asarray(b.total_bw, np.float64) for b in (on_chip, on_cpu))
        e_t, e_c = (
            np.asarray(b.errors_combined, np.float64) for b in (on_chip, on_cpu)
        )
        bw_rel = float(np.max(np.abs(bw_t - bw_c) / np.abs(bw_c)))
        err_abs = float(np.max(np.abs(e_t - e_c)))
        pct_t, pct_c = e_t.reshape(-1) * 100.0, e_c.reshape(-1) * 100.0
        med_t, med_c = float(np.median(pct_t)), float(np.median(pct_c))
        p95_t, p95_c = (float(np.percentile(p, 95)) for p in (pct_t, pct_c))
        _log(
            f"  {label}: {n_w} workloads x {n_p} placements | "
            f"max rel total_bw diff {bw_rel:.3e} | max abs error diff "
            f"{err_abs:.3e} | median % chip {med_t:.6f} cpu {med_c:.6f} | "
            f"p95 % chip {p95_t:.6f} cpu {p95_c:.6f}"
        )
        _log(
            f"    set-up {setup_s:.2f} s, one-run wall {run_s:.3f} s on the chip; "
            f"CPU reference set-up {cpu_setup_s:.2f} s, one-run {cpu_run_s:.3f} s"
        )
        assert bw_rel <= SWEEP_BW_RTOL, (label, "total_bw", bw_rel)
        assert err_abs <= SWEEP_ERR_ATOL, (label, "errors_combined", err_abs)
        assert abs(med_t - med_c) <= SWEEP_PCT_ATOL, (label, med_t, med_c)
        assert abs(p95_t - p95_c) <= SWEEP_PCT_ATOL, (label, p95_t, p95_c)


def phase_grouped(chip, reference) -> None:
    from repro.core.numa import (
        E5_2630_V3,
        E5_2630_V3_MIXED_DIMM,
        E5_2630_V3_THROTTLED,
        E5_2699_V3,
        E5_2699_V3_SNC2,
        E7_4830_V3,
        E7_8860_V3,
        simulate,
        thread_class_starts,
    )
    from repro.core.numa.benchmarks import benchmark_workload
    from repro.core.numa.workload import Workload

    worst = 0.0
    t0 = time.perf_counter()
    for machine in (
        E5_2630_V3, E5_2699_V3, E7_4830_V3, E7_8860_V3, E5_2699_V3_SNC2,
        E5_2630_V3_THROTTLED, E5_2630_V3_MIXED_DIMM,
    ):
        row = []
        for bench in ("CG", "Swim", "EP", "Page rank"):
            n = 2 * machine.cores_per_node
            n -= n % machine.n_nodes
            wl = benchmark_workload(bench, n)
            classes = thread_class_starts(wl)
            rng = np.random.default_rng(
                zlib.crc32(f"{machine.name}/{bench}".encode())
            )
            placements = jnp.asarray(
                np.stack([_random_placement(machine, n, rng) for _ in range(3)])
            )

            def grouped(arrays, p, machine=machine, classes=classes):
                return simulate(
                    machine, Workload("g", *arrays), p, thread_classes=classes
                )

            def per_thread(arrays, p, machine=machine):
                return reference(machine, Workload("r", *arrays), p)

            arrays = tuple(wl[1:])
            a = jax.jit(jax.vmap(grouped, in_axes=(None, 0)))(arrays, placements)
            b = jax.jit(jax.vmap(per_thread, in_axes=(None, 0)))(arrays, placements)
            _check_on((a, b), chip, f"grouped {machine.name}/{bench}")
            d = float(np.max(np.abs(np.asarray(a.rates) - np.asarray(b.rates))))
            worst = max(worst, d)
            row.append(f"{bench} {d:.2e}")
            assert d <= RATE_TOL, (machine.name, bench, "rates", d)
            for ga, gb in zip(
                jax.tree.leaves((a.read_flows, a.write_flows, a.sample)),
                jax.tree.leaves((b.read_flows, b.write_flows, b.sample)),
            ):
                np.testing.assert_allclose(
                    np.asarray(ga), np.asarray(gb), rtol=1e-5, atol=1e-4,
                    err_msg=f"{machine.name}/{bench}",
                )
            np.testing.assert_allclose(
                np.asarray(a.throughput), np.asarray(b.throughput), rtol=1e-5,
                err_msg=f"{machine.name}/{bench}",
            )
        _log(f"  {machine.name}: max |grouped - reference| rate: " + ", ".join(row))
    _log(
        f"  worst rate diff {worst:.3e} (bound {RATE_TOL}); "
        f"{time.perf_counter() - t0:.1f} s wall, compilation included"
    )


def phase_search(chip, cpu) -> None:
    from repro.core.numa import (
        branch_and_bound,
        make_machine,
        optimize_placement,
        thread_class_starts,
    )
    from repro.core.numa.benchmarks import benchmark_workload
    from repro.core.numa.simulator import simulate_grouped_batch

    m16 = make_machine(
        "snc2-8s", sockets=8, cores_per_socket=8, nodes_per_socket=2,
        qpi_bw=25.6e9,
    )
    wl = benchmark_workload("CG", 32)

    def search():
        best = optimize_placement(m16, wl)
        return best, branch_and_bound(
            m16, wl, gap=0.01, seed_placements=[best.placement]
        )

    t0 = time.perf_counter()
    best, cert = search()
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    best, cert = search()
    run_s = time.perf_counter() - t0
    with jax.default_device(cpu):
        cbest, ccert = search()
    _log(
        f"  chip: optimize {best.placement} -> B&B {cert.placement} "
        f"objective {cert.objective:.9e} optimal={cert.optimal} "
        f"({cert.nodes_expanded} nodes, {cert.evaluations} evaluations)"
    )
    _log(
        f"  cpu:  optimize {cbest.placement} -> B&B {ccert.placement} "
        f"objective {ccert.objective:.9e} optimal={ccert.optimal}"
    )
    _log(f"  set-up {setup_s:.2f} s, one-run wall {run_s:.3f} s on the chip")
    assert cert.optimal and ccert.optimal, (cert.optimal, ccert.optimal)
    assert cert.placement == ccert.placement, (cert.placement, ccert.placement)
    rel = abs(cert.objective - ccert.objective) / abs(ccert.objective)
    _log(f"  chip vs CPU objective rel diff {rel:.3e}")
    assert rel <= OBJ_RTOL, rel
    # the certified placement re-scored through the public batched solver
    sim = simulate_grouped_batch(
        m16, wl, jnp.asarray([cert.placement], jnp.int32),
        thread_classes=thread_class_starts(wl),
    )
    _check_on(sim, chip, "search re-score")
    got = float(np.asarray(sim.instructions).sum())
    assert abs(got - cert.objective) <= OBJ_RTOL * abs(cert.objective), (
        got, cert.objective,
    )


def phase_calibration(chip) -> None:
    from repro.core.numa import E5_2699_V3_SNC2
    from repro.core.numa.calibrate import (
        blind_template,
        collect_sweep,
        fit_machine,
        link_relative_errors,
        local_bw_relative_errors,
    )

    truth = E5_2699_V3_SNC2
    t0 = time.perf_counter()
    samples = collect_sweep(truth)
    result = fit_machine(blind_template(truth), samples, steps=200)
    fit_s = time.perf_counter() - t0
    _check_on((samples, result.params), chip, "calibration")
    link_err = link_relative_errors(result.machine, truth)
    local = local_bw_relative_errors(result.machine, truth)
    _log(
        f"  {truth.name}: {samples.n_samples} probes, loss "
        f"{result.seed_loss:.3e} -> {result.final_loss:.3e}, max link error "
        f"{float(link_err.max()):.4%}, max local read/write error "
        f"{float(local['read'].max()):.4%} / {float(local['write'].max()):.4%}"
    )
    _log(f"  set-up + fit {fit_s:.2f} s wall, compilation included")
    assert np.isfinite(result.final_loss), result.final_loss
    assert float(link_err.max()) <= LINK_ERR_MAX, link_err


def phase_service(chip) -> None:
    from repro.core.numa import E7_4830_V3, make_machine
    from repro.core.numa.evaluate import enumerate_placements
    from repro.core.numa.search import exact_objectives
    from repro.launch.advisor_serve import signature_pool
    from repro.serve import AdvisorService
    from repro.serve import service as service_mod

    m16 = make_machine(
        "snc2-8s", sockets=8, cores_per_socket=8, nodes_per_socket=2,
        qpi_bw=25.6e9,
    )
    sigs = signature_pool(40, seed=11)
    with AdvisorService() as svc:  # no deadline: a failure raises
        h4 = svc.register(E7_4830_V3)
        h16 = svc.register(m16)
        t0 = time.perf_counter()
        svc.warmup(h4, 24)
        svc.warmup(h16, 32)
        _log(f"  warmup {time.perf_counter() - t0:.2f} s (compilation included)")
        assert not svc.uses_search(E7_4830_V3, 24) and svc.uses_search(m16, 32)
        svc.metrics.reset(keep_traces=True)
        jit_traces = service_mod._advise_batch_jit._cache_size()

        misses: dict[int, object] = {}
        lock = threading.Lock()

        def caller(idx):
            for i in idx:
                adv = svc.query(h4, sigs[i], 24, timeout=600)
                with lock:
                    misses[i] = adv

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=caller, args=(range(k, len(sigs), 8),))
            for k in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
            assert not t.is_alive(), "service caller did not finish"
        miss_s = time.perf_counter() - t0
        hits = [svc.query(h4, s, 24, timeout=60) for s in sigs]
        search_adv = svc.query(h16, sigs[0], 32, timeout=900)
        sched = svc.query_schedule(
            h4, [(sigs[1], 1.0), (sigs[2], 2.0), (sigs[3], 1.0)], 24,
            timeout=900,
        )
        snap = svc.metrics.snapshot()
        traces_after = service_mod._advise_batch_jit._cache_size()

    assert len(misses) == len(sigs)
    for i, adv in misses.items():
        assert adv.fidelity == "exact" and adv.tier == "batch", adv
        assert hits[i] is adv, "a repeat did not come from the cache"
    assert search_adv.fidelity == "exact" and search_adv.tier == "search"
    assert search_adv.optimal, search_adv
    assert sched.tier == "schedule" and len(sched.placements) == 3, sched
    assert sched.gain_pct >= 0.0, sched
    counts = snap["tier_counts"]
    assert counts.get("batch", 0) == len(sigs), counts
    assert counts.get("cache", 0) == len(sigs), counts
    assert snap["fidelity_counts"].get("exact", 0) == snap["queries"], snap
    assert snap["retraces"] == 0 and traces_after == jit_traces, (
        snap["retraces"], jit_traces, traces_after,
    )

    # each miss answer against a serial, one-signature-at-a-time exact
    # evaluation of the same placement table on the chip
    placements = np.asarray(enumerate_placements(E7_4830_V3, 24))
    same, ties = 0, 0
    for i, sig in enumerate(sigs):
        obj = exact_objectives(E7_4830_V3, sig.workload(24), placements)
        j = int(np.argmax(obj))
        adv = misses[i]
        k = next(
            r for r, p in enumerate(placements) if tuple(int(v) for v in p)
            == adv.placement
        )
        assert abs(adv.objective - obj[j]) <= OBJ_RTOL * abs(obj[j]), (
            i, adv.objective, obj[j],
        )
        if k == j:
            same += 1
        else:  # a different placement of equal work rate
            assert abs(obj[k] - obj[j]) <= OBJ_RTOL * abs(obj[j]), (i, k, j)
            ties += 1
    _log(
        f"  {len(sigs)} misses in {snap['batch_calls']} micro-batches "
        f"({miss_s:.3f} s wall), {len(sigs)} cache hits, 1 search "
        f"{search_adv.placement}, 1 schedule (gain {sched.gain_pct:.3f}%), "
        f"retraces {snap['retraces']}, fidelity {snap['fidelity_counts']}"
    )
    _log(
        f"  serial argmax agrees: {same} identical placements, "
        f"{ties} ties of equal work rate"
    )


def main() -> int:
    try:
        from repro.runtime.compile_cache import use_compile_cache
    except ImportError as exc:
        sys.exit(f"chip_smoke: cannot import the repository's src/ ({exc})")
    cache_dir = use_compile_cache()

    from repro.core.numa import simulator

    devices = jax.devices()
    chip = devices[0]
    if chip.platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU found (jax.devices() = {devices}); "
            "this check runs on the chip only"
        )
    cpu = jax.devices("cpu")[0]
    _log(
        f"device: platform={chip.platform} kind={chip.device_kind} "
        f"count={len(devices)}; CPU reference {cpu}; "
        f"jax {jax.__version__}; compile cache {cache_dir}"
    )

    # Phases 2-6 must stay on the grouped solver: simulate's fallback to
    # the per-thread path (for traced workloads without thread classes)
    # fails the run.  Phase 3 calls the reference by its own name.
    reference = simulator.simulate_reference

    def _fallback(*args, **kwargs):
        raise AssertionError("simulate fell back to simulate_reference")

    simulator.simulate_reference = _fallback

    t_all = time.perf_counter()
    for name, run in (
        ("sweeps: chip vs in-process CPU", lambda: phase_sweeps(chip, cpu)),
        ("grouped vs reference on the chip", lambda: phase_grouped(chip, reference)),
        ("search on the 16-node machine", lambda: phase_search(chip, cpu)),
        ("calibration round trip", lambda: phase_calibration(chip)),
        ("advisor service", lambda: phase_service(chip)),
    ):
        _log(f"phase: {name}")
        t0 = time.perf_counter()
        run()
        _log(f"  ok ({time.perf_counter() - t0:.1f} s)")
    _log(f"all phases ok in {time.perf_counter() - t_all:.1f} s")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": chip.platform,
            "kind": chip.device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
