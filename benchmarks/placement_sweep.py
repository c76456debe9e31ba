"""Throughput benchmark for the batched multi-socket placement-sweep engine.

Sweeps one-thread-per-core placements through the single jitted
``evaluate_batch`` trace and reports

* placements/sec (fit + simulate + predict + error, per placement,
  steady-state after compilation), and
* the median model error as % of run bandwidth (paper's headline metric:
  2.34% at s = 2).

Three machines are swept: the fully-connected quad-socket preset (1469
compositions of 24 threads — the paper's §6.2.2 protocol at beyond-paper
socket count), the glued 8-socket preset, whose node-controller topology
routes cross-quad traffic over 2 links (a deterministic budget samples
its combinatorial placement space), and the SNC-2 variant of the 18-core
2-socket machine, whose 4 half-socket NUMA nodes share one QPI port per
socket.

Run directly:

    PYTHONPATH=src python benchmarks/placement_sweep.py [--json OUT.json]

``--json`` artifacts are uploaded by CI and gated against the committed
baseline (``benchmarks/sweep_baseline.json``) by
``benchmarks/check_sweep_regression.py``.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np


def numa_placement_sweep(
    machine=None,
    n_threads: int | None = None,
    *,
    benchmarks: tuple[str, ...] = ("Swim", "CG", "EP", "NPO"),
    noise_std: float = 0.02,
    min_placements: int = 500,
    max_placements: int | None = None,
) -> tuple[float, dict]:
    """Returns ``(placements_per_sec, details)`` for the harness."""
    from repro.core.numa import E7_4830_V3
    from repro.core.numa.benchmarks import benchmark_workload
    from repro.core.numa.evaluate import evaluate_batch, sweep_placements

    if machine is None:
        machine = E7_4830_V3
    if n_threads is None:
        n_threads = 2 * machine.cores_per_node  # the largest sweep space

    placements = sweep_placements(
        machine, n_threads, max_placements=max_placements
    )
    n_p = placements.shape[0]
    assert n_p >= min_placements, (n_p, min_placements)
    workloads = [benchmark_workload(b, n_threads) for b in benchmarks]
    keys = jax.numpy.stack(
        [jax.random.fold_in(jax.random.PRNGKey(0), i) for i in range(len(workloads))]
    )

    def run():
        batch = evaluate_batch(
            machine, workloads, placements, noise_std=noise_std, keys=keys
        )
        jax.block_until_ready(batch.errors_combined)
        return batch

    t0 = time.time()
    batch = run()  # includes compilation
    compile_s = time.time() - t0
    t0 = time.time()
    batch = run()  # steady state (one cached trace)
    steady_s = time.time() - t0

    evaluated = n_p * len(workloads)
    errors_pct = np.asarray(batch.errors_combined).reshape(-1) * 100.0
    details = {
        "machine": machine.name,
        "topology": machine.topology.name,
        "n_links": machine.n_links,
        "max_hops": machine.topology.max_hops,
        "sockets": machine.sockets,
        "n_nodes": machine.n_nodes,
        "n_threads": n_threads,
        "placements": n_p,
        "benchmarks": len(workloads),
        "median_error_pct": round(float(np.median(errors_pct)), 4),
        "p95_error_pct": round(float(np.percentile(errors_pct, 95)), 4),
        "compile_s": round(compile_s, 3),
        "steady_s": round(steady_s, 3),
    }
    return evaluated / steady_s, details


def glued8s_placement_sweep(
    *, max_placements: int = 512, **kwargs
) -> tuple[float, dict]:
    """The routed 8-socket sweep: cross-quad flows charge both links of
    their node-controller route and pay the per-hop remote attenuation."""
    from repro.core.numa import E7_8860_V3

    kwargs.setdefault("min_placements", min(500, max_placements))
    return numa_placement_sweep(
        E7_8860_V3, max_placements=max_placements, **kwargs
    )


def snc2_placement_sweep(**kwargs) -> tuple[float, dict]:
    """The sub-NUMA-clustered sweep: the 18-core 2-socket machine in SNC-2
    mode places 16 threads over 4 half-socket NUMA nodes (633 compositions
    under the 9-core per-node cap); cross-socket traffic from a
    non-endpoint node routes through its socket's shared QPI port."""
    from repro.core.numa import E5_2699_V3_SNC2

    kwargs.setdefault("min_placements", 500)
    return numa_placement_sweep(E5_2699_V3_SNC2, n_threads=16, **kwargs)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--json",
        type=Path,
        default=None,
        help="write results as a JSON artifact (for CI upload/trending)",
    )
    parser.add_argument(
        "--glued-max-placements",
        type=int,
        default=512,
        help="deterministic placement budget for the 8-socket sweep",
    )
    args = parser.parse_args()

    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    records = []
    for label, fn in (
        ("4-socket fully-connected", numa_placement_sweep),
        (
            "8-socket glued (routed)",
            lambda: glued8s_placement_sweep(
                max_placements=args.glued_max_placements
            ),
        ),
        ("2-socket SNC-2 (4 nodes)", snc2_placement_sweep),
    ):
        pps, details = fn()
        records.append({"sweep": label, "placements_per_sec": round(pps, 1), **details})
        print(f"{label}: placements/sec: {pps:,.0f}")
        for k, v in details.items():
            print(f"  {k}: {v}")

    if args.json is not None:
        args.json.parent.mkdir(parents=True, exist_ok=True)
        args.json.write_text(json.dumps(records, indent=2) + "\n")
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
