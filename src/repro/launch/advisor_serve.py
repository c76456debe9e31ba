"""Load driver + CLI for the placement-advisor service.

Spins up an :class:`~repro.serve.AdvisorService` over the NUMA presets
and drives a mixed query stream against it, printing the per-tier
metrics snapshot (counts, batch histogram, p50/p99 latency, retraces).
The driver functions here are also the engine of
``benchmarks/advisor_serve.py``, which commits qps floors and p99
ceilings to CI.

    PYTHONPATH=src python -m repro.launch.advisor_serve \
        --queries 1000 --pool 32 --hit-fraction 0.8 --workers 4
"""

from __future__ import annotations

import argparse
import itertools
import json
import threading
import time

import numpy as np

from repro.serve import AdvisorService, QuerySignature


def signature_pool(
    n: int,
    *,
    read_bpi: float = 0.6,
    write_bpi: float = 0.2,
    seed: int = 0,
) -> list[QuerySignature]:
    """``n`` deterministic distinct workload signatures: mixes drawn from
    a Dirichlet (interleaved takes the 4th share, scaled so every mix sums
    under 1), rounded so canonicalization keeps them distinct."""
    rng = np.random.default_rng(seed)
    sigs = []
    for _ in range(n):
        read = rng.dirichlet(np.ones(4))[:3] * 0.9
        write = rng.dirichlet(np.ones(4))[:3] * 0.9
        sigs.append(
            QuerySignature(
                tuple(round(float(v), 4) for v in read),
                tuple(round(float(v), 4) for v in write),
                read_bpi,
                write_bpi,
            )
        )
    return sigs


def drive_async(service: AdvisorService, queries) -> tuple[list, float]:
    """Open-loop load: submit the whole stream without waiting (concurrent
    misses coalesce into micro-batches), then drain every future.
    ``queries`` is a list of ``(machine_or_fp, signature, n_threads)``.
    Returns (advice list, wall seconds)."""
    t0 = time.perf_counter()
    futures = [service.submit(m, sig, n) for (m, sig, n) in queries]
    results = [f.result() for f in futures]
    wall = time.perf_counter() - t0
    return results, wall


def drive_threads(
    service: AdvisorService, queries, *, n_workers: int = 4,
    deadline_s: float | None = None,
) -> tuple[list, float]:
    """Closed-loop load: ``n_workers`` threads issue synchronous queries,
    each pulling the next query off a shared counter.  ``deadline_s``
    arms the service's degradation ladder per query (None = wait for the
    exact answer).  Returns (advice list in query order, wall seconds)."""
    results: list = [None] * len(queries)
    counter = itertools.count()

    def worker() -> None:
        while True:
            i = next(counter)
            if i >= len(queries):
                return
            machine, sig, n = queries[i]
            results[i] = service.query(
                machine, sig, n, deadline_s=deadline_s
            )

    threads = [
        threading.Thread(target=worker, name=f"advisor-load-{w}")
        for w in range(n_workers)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return results, wall


def mixed_stream(
    pool: list[QuerySignature],
    fresh: list[QuerySignature],
    search_sigs: list[QuerySignature],
    n_queries: int,
    *,
    sweep_target,
    search_target,
    hit_fraction: float = 0.8,
    search_fraction: float = 0.02,
    seed: int = 1,
) -> list[tuple]:
    """A deterministic shuffled stream mixing cache hits (drawn from
    ``pool``, assumed pre-answered), fresh sweep misses (consumed from
    ``fresh``), and search-tier queries (drawn from ``search_sigs``,
    assumed warmed).  ``*_target`` are ``(machine_or_fp, n_threads)``."""
    rng = np.random.default_rng(seed)
    fresh_iter = iter(fresh)
    stream: list[tuple] = []
    for _ in range(n_queries):
        roll = rng.random()
        if roll < search_fraction:
            sig = search_sigs[int(rng.integers(len(search_sigs)))]
            stream.append((search_target[0], sig, search_target[1]))
        elif roll < search_fraction + (1.0 - hit_fraction - search_fraction):
            sig = next(fresh_iter, None)
            if sig is None:  # fresh supply exhausted -> serve a hit instead
                sig = pool[int(rng.integers(len(pool)))]
            stream.append((sweep_target[0], sig, sweep_target[1]))
        else:
            sig = pool[int(rng.integers(len(pool)))]
            stream.append((sweep_target[0], sig, sweep_target[1]))
    return stream


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--queries", type=int, default=1000)
    parser.add_argument("--pool", type=int, default=32,
                        help="distinct signatures in the hot (cached) set")
    parser.add_argument("--hit-fraction", type=float, default=0.8)
    parser.add_argument("--search-fraction", type=float, default=0.02)
    parser.add_argument("--workers", type=int, default=4)
    parser.add_argument("--max-batch", type=int, default=8)
    parser.add_argument("--max-wait-ms", type=float, default=2.0)
    parser.add_argument("--deadline-ms", type=float, default=None,
                        help="per-query deadline (ms); past it the answer "
                             "comes off the degradation ladder")
    parser.add_argument("--json", type=str, default=None,
                        help="write the metrics snapshot to this path")
    args = parser.parse_args()

    from repro.core.numa import E7_4830_V3, make_machine
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()

    service = AdvisorService(
        max_batch=args.max_batch, max_wait_s=args.max_wait_ms / 1e3
    )
    sweep_fp = service.register(E7_4830_V3)
    m16 = make_machine(
        "snc2-8s", sockets=8, cores_per_socket=8, nodes_per_socket=2,
        qpi_bw=25.6e9,
    )
    search_fp = service.register(m16)

    pool = signature_pool(args.pool, seed=0)
    fresh = signature_pool(args.queries, seed=7)
    search_sigs = signature_pool(2, seed=13)

    print("warming up (jit traces + search-tier caches)...")
    service.warmup(sweep_fp, 24)
    for sig in pool:  # pre-answer the hot set
        service.query(sweep_fp, sig, 24)
    for sig in search_sigs:
        service.query(search_fp, sig, 32)
    service.metrics.reset(keep_traces=True)

    stream = mixed_stream(
        pool, fresh, search_sigs, args.queries,
        sweep_target=(sweep_fp, 24), search_target=(search_fp, 32),
        hit_fraction=args.hit_fraction,
        search_fraction=args.search_fraction,
    )
    deadline_s = None if args.deadline_ms is None else args.deadline_ms / 1e3
    results, wall = drive_threads(
        service, stream, n_workers=args.workers, deadline_s=deadline_s
    )
    assert all(r is not None for r in results)

    snap = service.metrics.snapshot()
    snap["qps"] = round(len(stream) / wall, 1)
    snap["wall_s"] = round(wall, 3)
    print(json.dumps(snap, indent=2))
    if args.json and args.json != "-":
        with open(args.json, "w") as fh:
            json.dump(snap, fh, indent=2)
        print(f"wrote {args.json}")
    service.close()


if __name__ == "__main__":
    main()
