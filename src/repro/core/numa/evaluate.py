"""Evaluation harness reproducing the paper's §6 methodology — batched.

* ``sweep_placements`` / ``enumerate_placements``: every thread
  distribution over ``s >= 2`` sockets keeping one thread per core
  (compositions of ``n_threads``), with a deterministic subsampling budget
  for the combinatorial counts that appear at 4+ sockets.
* ``evaluate_batch``: the single jitted entry point — fit each workload's
  signature from the 2 profiling runs, then predict the bank counters of
  *every* placement and compare against (simulated) measurements, vmapped
  over placements *and* benchmarks in one trace (paper §6.2.2 at the
  paper's "thousands of measurements" scale).
* ``evaluate_accuracy`` / ``evaluate_suite``: thin routes through
  ``evaluate_batch`` (paper Figures 16–18).
* ``evaluate_stability``: fit the same workload on two machines and measure
  how much bandwidth the signature reallocates — one batched fit trace per
  machine (paper §6.2.1 / Figures 13–15).

Errors are reported the paper's way: per counter measurement, as a
percentage of the run's total bandwidth.  Fitted signatures are cached
keyed on ``(machine, workload, noise, key)`` so repeated evaluations (the
advisor's inner loop) never re-profile.
"""

from __future__ import annotations

import hashlib
import itertools
import math
import random as _pyrandom
import threading
from collections.abc import Sized
from functools import lru_cache, partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.bwsig import (
    BandwidthSignature,
    DirectionSignature,
    fit_signature,
    misfit_score,
    predict_counters,
    signature_distance,
)
from repro.core.numa.benchmarks import benchmark_workload, suite_names
from repro.core.numa.machine import MachineSpec, canonical_bank_assignment
from repro.core.numa.simulator import (
    noise_factor,
    profile_pair,
    simulate,
    simulate_grouped_batch,
    support_patterns,
    thread_class_starts,
)
from repro.core.numa.workload import Workload
from repro.runtime.tracing import span

# ---------------------------------------------------------------------------
# Placement enumeration: compositions of n_threads over s sockets
# ---------------------------------------------------------------------------


@lru_cache(maxsize=256)
def _composition_table(s: int, cap: int, n: int) -> tuple[tuple[int, ...], ...]:
    """``T[k][m]``: number of compositions of ``m`` into ``k`` ordered parts
    each in ``[0, cap]`` (python ints — exact at any scale).  Cached:
    every ``count_placements`` / ``enumerate_placements`` call used to
    rebuild the full DP table (~``s * n * cap`` bigint additions) even
    for the same machine geometry; the sweep drivers hit a handful of
    ``(s, cap, n)`` keys thousands of times."""
    T = [[0] * (n + 1) for _ in range(s + 1)]
    T[0][0] = 1
    for k in range(1, s + 1):
        prev, cur = T[k - 1], T[k]
        for m in range(n + 1):
            cur[m] = sum(prev[m - j] for j in range(min(cap, m) + 1))
    return tuple(tuple(row) for row in T)


def _unrank_compositions(
    table: tuple[tuple[int, ...], ...], ranks, s: int, cap: int, n: int
) -> np.ndarray:
    """Vectorized unranking of composition ``ranks`` through the counting
    table: one numpy pass per position instead of a per-rank python loop
    over ``s * cap`` table cells.  Falls back to the exact-bigint python
    loop when any table entry overflows int64 (possible from ~20 nodes
    up — far beyond any preset; the int64 path is bit-exact below that)."""
    ranks = list(ranks)
    out = np.empty((len(ranks), s), np.int32)
    if not ranks:
        return out
    if max(max(row) for row in table) < 2**62:  # every table entry fits int64
        T = np.asarray(table, np.int64)  # (s+1, n+1)
        r = np.asarray(ranks, np.int64)
        m = np.full(r.shape, n, np.int64)
        j_grid = np.arange(cap + 1, dtype=np.int64)
        for k in range(s, 0, -1):
            idx = m[:, None] - j_grid[None, :]  # (R, cap+1)
            counts = np.where(idx >= 0, T[k - 1][np.clip(idx, 0, None)], 0)
            csum = counts.cumsum(axis=1)
            j = (csum <= r[:, None]).sum(axis=1)  # first j with r < csum[j]
            prev = np.take_along_axis(csum, np.maximum(j - 1, 0)[:, None], 1)[:, 0]
            r = r - np.where(j > 0, prev, 0)
            out[:, s - k] = j
            m = m - j
        return out
    for row, rank in enumerate(ranks):
        r, m = rank, n
        for k in range(s, 0, -1):
            for j in range(min(cap, m) + 1):
                c = table[k - 1][m - j]
                if r < c:
                    out[row, s - k] = j
                    m -= j
                    break
                r -= c
    return out


def count_placements(machine: MachineSpec, n_threads: int) -> int:
    """How many one-thread-per-core distributions of ``n_threads`` over the
    machine's NUMA nodes exist."""
    table = _composition_table(machine.n_nodes, machine.cores_per_node, n_threads)
    return table[machine.n_nodes][n_threads]


def enumerate_placements(
    machine: MachineSpec,
    n_threads: int,
    *,
    max_placements: int | None = None,
    seed: int = 0,
) -> Array:
    """All (or a deterministic sample of) thread distributions over the
    machine's NUMA nodes keeping one thread per core — the s >= 2
    generalization of the paper's §6.2.2 sweep, with per-node core caps
    (``cores_per_node``, so SNC machines never overfill a half-socket
    domain).

    Placements are emitted in lexicographic order (node-0 count
    ascending), which at ``s = 2`` is exactly the classic ``[i, n - i]``
    sweep.  When the composition count exceeds ``max_placements`` a
    uniform sample of ranks (seeded, deterministic) is drawn and unranked
    through the counting table, so huge 8-socket spaces never need to be
    materialized.

    The counting table is memoized per ``(s, cap, n)`` and unranking is
    numpy-vectorized over the whole rank batch (one pass per node
    position).  Benchmark: the full 1469-placement 4-socket enumeration
    dropped ~25x (8.5 ms -> 0.33 ms warm) and a 512-rank sample of the
    8-socket space ~10x (6.5 ms -> 0.65 ms) on the CI-class container —
    previously every sweep/advisor call rebuilt the DP table and walked
    a python loop per rank.
    """
    s, cap = machine.n_nodes, machine.cores_per_node
    if not 0 <= n_threads <= s * cap:
        raise ValueError(
            f"{n_threads} threads do not fit {s} nodes x {cap} cores"
        )
    table = _composition_table(s, cap, n_threads)
    total = table[s][n_threads]
    if max_placements is not None and total > max_placements:
        ranks: Sequence[int] = sorted(
            _pyrandom.Random(seed).sample(range(total), max_placements)
        )
    else:
        ranks = range(total)
    return jnp.asarray(_unrank_compositions(table, ranks, s, cap, n_threads))


def sweep_placements(
    machine: MachineSpec,
    n_threads: int,
    *,
    max_placements: int | None = None,
    seed: int = 0,
) -> Array:
    """All thread distributions that keep one thread per core (paper
    §6.2.2: "varied the distribution of the threads between the two
    sockets maintaining a single thread per core") — generalized to any
    NUMA-node count via :func:`enumerate_placements`."""
    return enumerate_placements(
        machine, n_threads, max_placements=max_placements, seed=seed
    )


# ---------------------------------------------------------------------------
# The batched fit + predict engine
# ---------------------------------------------------------------------------


class AccuracyResult(NamedTuple):
    """Fit-and-predict accuracy of the model on one workload: per-counter
    prediction errors over a placement sweep, as fractions of run
    bandwidth (the paper's §6.2 evaluation protocol)."""

    placements: Array  # (P, s)
    errors_read: Array  # (P, 2s) |pred-meas| as fraction of run bandwidth
    errors_write: Array  # (P, 2s)
    errors_combined: Array  # (P, 2s)
    total_bw: Array  # (P,) bytes/s moved by the run
    misfit: Array  # scalar §6.2.1 detector score
    signature: BandwidthSignature


class BatchAccuracy(NamedTuple):
    """`evaluate_batch` output: leading axis = benchmark (B), then placement."""

    placements: Array  # (P, s)
    errors_read: Array  # (B, P, 2s)
    errors_write: Array  # (B, P, 2s)
    errors_combined: Array  # (B, P, 2s)
    total_bw: Array  # (B, P)
    misfit: Array  # (B,)
    signatures: BandwidthSignature  # leaves stacked over B
    combined_signatures: BandwidthSignature  # leaves stacked over B


def _direction_errors(sig_dir, placement, flows, local_meas, remote_meas):
    demand = flows.sum(axis=1)
    pred_local, pred_remote = predict_counters(sig_dir, demand, placement)
    return jnp.concatenate(
        [jnp.abs(pred_local - local_meas), jnp.abs(pred_remote - remote_meas)]
    )


def _batched_direction_errors(
    sig_dir, pt, il, used, demand, local_meas, remote_meas
):
    """:func:`_direction_errors` for a whole placement batch at once.

    ``predict_counters`` only ever reads the diagonal and the column sums
    of the predicted ``(s, s)`` flow matrix, and every term of the §4
    placement matrix is rank-1 in the bank axis — so both counters close
    over ``(P, s)`` element-wise math without materializing a per-placement
    matrix:

        pred[i, j] = demand_i * (sf*st_j + lf*δij + pf*pt_j
                                 + inter * used_i * used_j / s_used)
        local[j]   = pred[j, j]
        remote[j]  = sum_i pred[i, j] - local[j]

    ``pt`` and ``il`` are the per-thread and interleave rows (``(P, s)``,
    shared with the simulator's slab build), ``used`` the support mask."""
    s = pt.shape[-1]
    st = (jnp.arange(s) == sig_dir.static_socket).astype(pt.dtype)  # (s,)
    inter = jnp.clip(
        1.0
        - sig_dir.static_fraction
        - sig_dir.local_fraction
        - sig_dir.per_thread_fraction,
        0.0,
        1.0,
    )
    total = demand.sum(axis=1, keepdims=True)  # (P, 1)
    total_used = (demand * used).sum(axis=1, keepdims=True)
    colw = (
        sig_dir.static_fraction * st[None, :]
        + sig_dir.per_thread_fraction * pt
        + inter * il
    )  # (P, s): the bank-axis weights shared by every used row
    local = demand * (colw + sig_dir.local_fraction)
    colsum = (
        sig_dir.static_fraction * st[None, :]
        + sig_dir.per_thread_fraction * pt
    ) * total + inter * il * total_used + sig_dir.local_fraction * demand
    remote = colsum - local
    return jnp.concatenate(
        [jnp.abs(local - local_meas), jnp.abs(remote - remote_meas)], axis=1
    )


def _workload_arrays(wl: Workload) -> tuple[Array, ...]:
    """The array fields of a Workload (everything but the name) — the jit
    boundary cannot carry the string leaf."""
    return tuple(wl[1:])


def _as_workload_list(
    workloads: Workload | Sequence[Workload],
) -> list[Workload]:
    wl_list = [workloads] if isinstance(workloads, Workload) else list(workloads)
    n_threads = {w.n_threads for w in wl_list}
    if len(n_threads) != 1:
        raise ValueError(f"workloads must share a thread count, got {n_threads}")
    return wl_list


def _memo_get(cache: dict, lock: threading.RLock, key):
    """LRU-touching lookup into an id-keyed memo cache: a hit re-inserts
    the entry at the young end (python dicts preserve insertion order), so
    hot keys survive eviction cycles.  Guarded by ``lock`` — the advisor
    service hammers these memos from concurrent threads."""
    with lock:
        hit = cache.pop(key, None)
        if hit is not None:
            cache[key] = hit
        return hit


def _memo_put(cache: dict, lock: threading.RLock, key, value, max_entries: int):
    """Bounded insert: evict oldest-first past ``max_entries`` (the memos
    used to grow per distinct object id for the life of the process under
    workloads that never repeat — the serving miss path is exactly that)."""
    with lock:
        cache[key] = value
        while len(cache) > max_entries:
            cache.pop(next(iter(cache)))


def _stack_workloads(wl_list: Sequence[Workload]) -> tuple[Array, ...]:
    """Stack each array field over a leading benchmark axis.

    Memoized on the workload objects' identities (the values keep the
    workloads alive, so ids cannot be recycled while a key is live):
    sweep/advisor loops re-evaluate the same suite hundreds of times and
    the ~40 small ``jnp.stack`` dispatches were a measurable slice of the
    per-call wall time.  LRU-bounded and lock-guarded (see
    :func:`_memo_get`): unbounded id-keyed growth and torn eviction were
    both real failure modes once the advisor service started calling this
    from many threads."""
    key = tuple(id(w) for w in wl_list)
    hit = _memo_get(_STACK_CACHE, _MEMO_LOCK, key)
    if hit is not None:
        return hit[1]
    stacked = tuple(
        jnp.stack(parts)
        for parts in zip(*(_workload_arrays(w) for w in wl_list))
    )
    _memo_put(
        _STACK_CACHE, _MEMO_LOCK, key, (tuple(wl_list), stacked),
        _MEMO_CACHE_MAX,
    )
    return stacked


_MEMO_LOCK = threading.RLock()
_MEMO_CACHE_MAX = 64
_STACK_CACHE: dict[tuple, tuple] = {}


def _support_arrays(placements: Array) -> tuple[Array, Array]:
    """Device-ready ``(support, slab_id)`` for a placement batch, memoized
    on the batch object's identity (the value keeps the batch alive) —
    the host-side ``np.unique`` bucketing is pure overhead when the same
    enumerated sweep is evaluated repeatedly.  Same LRU bound + lock as
    :func:`_stack_workloads`."""
    key = id(placements)
    hit = _memo_get(_SUPPORT_CACHE, _MEMO_LOCK, key)
    if hit is not None:
        return hit[1]
    support, slab_id = support_patterns(placements)
    value = (jnp.asarray(support), jnp.asarray(slab_id))
    _memo_put(
        _SUPPORT_CACHE, _MEMO_LOCK, key, (placements, value), _MEMO_CACHE_MAX
    )
    return value


_SUPPORT_CACHE: dict[int, tuple] = {}


def _normalize_keys(keys: Array | None, n: int) -> Array:
    """One PRNG key per workload: default PRNGKey(0), broadcast a single
    key, pass a (n, 2) stack through."""
    if keys is None:
        return jnp.stack([jax.random.PRNGKey(0)] * n)
    keys = jnp.asarray(keys)
    if keys.ndim == 1:
        keys = jnp.broadcast_to(keys, (n,) + keys.shape)
    return keys


def _fit_one(machine, arrays, prof_key, noise_std, background_bw, thread_classes):
    wl = Workload("batched", *arrays)
    sym, asym = profile_pair(
        machine,
        wl,
        noise_std=noise_std,
        background_bw=background_bw,
        key=prof_key,
        thread_classes=thread_classes,
    )
    sig = fit_signature(sym, asym)
    sig_combined = fit_signature(sym, asym, combined=True)
    detector = misfit_score(sym, "read")
    return sig, sig_combined, detector


@partial(
    jax.jit,
    static_argnames=(
        "machine", "noise_std", "background_bw", "thread_classes", "multipath",
        "bank_assignment",
    ),
)
def _evaluate_batch_jit(
    machine: MachineSpec,
    wl_arrays: tuple[Array, ...],  # leaves carry a leading benchmark axis B
    placements: Array,  # (P, s)
    support: Array,  # (n_buckets, s) support patterns (host-bucketed)
    slab_id: Array,  # (P,) bucket of each placement
    base_keys: Array,  # (B, 2)
    noise_std: float,
    background_bw: float,
    thread_classes: tuple[int, ...],
    multipath: bool = False,
    bank_assignment: tuple[int, ...] | None = None,
):
    """One trace: vmap over benchmarks of (fit, then the shared-slab
    batched solver + batched noise/error tails).  ``thread_classes`` is
    the batch's common static class refinement
    (:func:`thread_class_starts`) — the workload arrays are traced here,
    so it must ride in as a static argument to keep every inner solve on
    the group-collapsed path.  ``support`` / ``slab_id`` carry the
    host-side support bucketing into the trace
    (:func:`repro.core.numa.simulator.support_patterns`): the base +
    interleave resource slab is built once per bucket and only the traced
    multiplicities and the rank-1 per-thread update vary per placement.

    Measurement noise is drawn in three batched ``(P, ...)`` draws per
    benchmark (split of the measurement key) instead of a per-placement
    key chain — same lognormal model, one RNG pass.

    The fit, the sweep and the noise and error tail run under the named
    scopes ``fit``, ``sweep`` and ``tail``; a profiler trace names each
    device operation by its scopes (``tf_op``), and the scopes change
    nothing else in the program.

    The last output holds, row by row, what the signature cache stores
    (:func:`_pack_rows`): each benchmark's profiling key (the first half
    of its base key's split, the key its fit consumed, which the cache
    files the fit under), its two signatures and its misfit score; and
    last the most trips the sweep fill's loop took over the benchmark's
    placements."""
    s = machine.n_nodes

    def per_benchmark(arrays, base_key):
        k_prof, k_meas = jax.random.split(base_key)
        with jax.named_scope("fit"):
            sig, sig_combined, detector = _fit_one(
                machine, arrays, k_prof, noise_std, background_bw, thread_classes
            )
        wl = Workload("batched", *arrays)
        with jax.named_scope("sweep"):
            sim = simulate_grouped_batch(
                machine,
                wl,
                placements,
                thread_classes=thread_classes,
                support=support,
                slab_id=slab_id,
                multipath=multipath,
                bank_assignment=bank_assignment,
            )
        with jax.named_scope("tail"):
            read_flows, write_flows = sim.read_flows, sim.write_flows
            if noise_std > 0.0 or background_bw > 0.0:
                # the error metrics never read the (noised) instruction
                # counters, so only the two flow draws are materialized
                kr, kw = jax.random.split(k_meas)
                read_flows = read_flows * noise_factor(
                    noise_std * jax.random.normal(kr, read_flows.shape)
                ) + background_bw / (s * s)
                write_flows = write_flows * noise_factor(
                    noise_std * jax.random.normal(kw, write_flows.shape)
                ) + background_bw / (s * s)

            local_read = jnp.diagonal(read_flows, axis1=1, axis2=2)  # (P, s)
            remote_read = read_flows.sum(axis=1) - local_read
            local_write = jnp.diagonal(write_flows, axis1=1, axis2=2)
            remote_write = write_flows.sum(axis=1) - local_write
            totals = jnp.maximum(
                read_flows.sum(axis=(1, 2)) + write_flows.sum(axis=(1, 2)), 1e-9
            )

            # batched §4 prediction: the placement-matrix terms are rank-1 in
            # the bank axis, so the counter errors close over (P, s) math
            # (guards mirror bwsig's _per_thread_matrix/_interleaved_matrix)
            nf = placements.astype(jnp.float32)
            pt = nf / jnp.maximum(nf.sum(axis=1, keepdims=True), 1.0)
            used = (nf > 0).astype(jnp.float32)
            il = used / jnp.maximum(used.sum(axis=1, keepdims=True), 1.0)
            inv = 1.0 / totals[:, None]
            e_read = inv * _batched_direction_errors(
                sig.read, pt, il, used,
                read_flows.sum(axis=2), local_read, remote_read,
            )
            e_write = inv * _batched_direction_errors(
                sig.write, pt, il, used,
                write_flows.sum(axis=2), local_write, remote_write,
            )
            e_comb = inv * _batched_direction_errors(
                sig_combined.read, pt, il, used,
                read_flows.sum(axis=2) + write_flows.sum(axis=2),
                local_read + local_write, remote_read + remote_write,
            )
        return (e_read, e_write, e_comb, totals, detector, sig, sig_combined,
                k_prof, sim.fill_trips.max())

    *outs, k_prof, trips = jax.vmap(per_benchmark)(wl_arrays, base_keys)
    detector, sig, sig_combined = outs[4:]
    return (*outs, _pack_rows((k_prof, sig, sig_combined, detector, trips)))


def _pack_rows(tree) -> Array:
    """The leaves of ``tree``, which share a leading axis, bitcast to
    uint32 and laid side by side in one ``(B, K)`` array: the host then
    fetches them in one transfer, and :func:`_unpack_rows` gives back
    every bit."""
    return jnp.concatenate(
        [
            jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(x.shape[0], -1)
            for x in jax.tree.leaves(tree)
        ],
        axis=1,
    )


def _unpack_rows(packed: np.ndarray, like):
    """The tree ``like`` (whose leaves give each shape and dtype) as NumPy
    arrays read out of the host copy of :func:`_pack_rows`' output."""
    leaves, treedef = jax.tree.flatten(like)
    widths = [
        math.prod(x.shape[1:]) * np.dtype(x.dtype).itemsize // 4 for x in leaves
    ]
    columns = np.split(packed, np.cumsum(widths)[:-1], axis=1)
    return jax.tree.unflatten(
        treedef,
        [
            np.ascontiguousarray(c).view(x.dtype).reshape(x.shape)
            for c, x in zip(columns, leaves)
        ],
    )


def evaluate_batch(
    machine: MachineSpec,
    workloads: Workload | Sequence[Workload],
    placements: Array,
    *,
    noise_std: float = 0.0,
    background_bw: float = 0.0,
    keys: Array | None = None,
    multipath: bool = False,
    bank_assignment=None,
) -> BatchAccuracy:
    """Fit + predict every workload over every placement in ONE jitted,
    doubly-vmapped trace, bucketing the placements by support pattern so
    the resource slab is built once per bucket (see
    :func:`repro.core.numa.simulator.simulate_grouped_batch`).

    ``keys`` is one PRNG key per workload (or a single key, split/shared
    exactly like :func:`evaluate_accuracy` does); defaults to
    ``PRNGKey(0)`` per workload.  Output rows stay in the caller's
    placement order — bucketing is an internal gather, not a reorder.

    ``bank_assignment`` applies one page placement to every simulated
    placement (``None`` = node-local; see
    :func:`repro.core.numa.machine.canonical_bank_assignment`).  The
    2-run profiling fit is *not* re-pointed — signatures describe the
    workload, not the placement — so cached signatures stay shared
    across bank assignments.

    A running profiler records the call as a ``repro.evaluate.batch``
    span tiled by three children (:mod:`repro.runtime.tracing`):
    ``repro.evaluate.prepare`` (workloads, keys, support buckets, the
    stacked arrays, thread classes), ``repro.evaluate.dispatch`` (the
    jitted call, which returns before the device finishes) and
    ``repro.evaluate.writeback`` (the signature cache).  All four carry
    the call's sequence number as ``call``.  The batch span also carries
    ``buckets``, the support patterns of the placements (one slab build
    each), and ``slab_rows``, the rows of one placement's grouped slab:
    thread classes times nodes, empty groups included.

    The writeback files each workload's fit in the signature cache under
    its profiling key, which the jitted trace returns.  It fetches those
    keys, the signatures and the misfit scores, packed into one array by
    the trace, in one transfer, and reads them out (span
    ``repro.evaluate.fetch``, stats ``leaves``, the arrays fetched, and
    ``fill_trips``, the most trips the sweep fill's loop took in the
    call, the trips of the batched loop), builds the cache
    keys from memoized workload fingerprints, and inserts the misses
    (span ``repro.evaluate.insert``, stat ``misses``).  The host path
    left around the device call is the prepare phase (key normalization,
    the memo lookups, ``thread_class_starts``) and the jitted call's own
    dispatch.
    """
    call = next(_CALLS)
    if not isinstance(workloads, (Workload, Sized)):
        workloads = list(workloads)  # counted here, read in prepare
    with span(
        "evaluate.batch", call=call,
        workloads=1 if isinstance(workloads, Workload) else len(workloads),
        placements=len(placements),
    ) as batch_span:
        with span("evaluate.prepare", call=call):
            wl_list = _as_workload_list(workloads)
            keys = _normalize_keys(keys, len(wl_list))
            placements = jnp.asarray(placements)
            support, slab_id = _support_arrays(placements)
            stacked = _stack_workloads(wl_list)
            thread_classes = thread_class_starts(wl_list)
            banks = canonical_bank_assignment(machine, bank_assignment)
        batch_span.set_metadata(
            buckets=int(support.shape[0]),
            slab_rows=len(thread_classes) * machine.n_nodes,
        )
        with span("evaluate.dispatch", call=call):
            e_read, e_write, e_comb, totals, misfit, sigs, csigs, packed = (
                _evaluate_batch_jit(
                    machine,
                    stacked,
                    placements,
                    support,
                    slab_id,
                    keys,
                    float(noise_std),
                    float(background_bw),
                    thread_classes,
                    multipath,
                    banks,
                )
            )
        with span("evaluate.writeback", call=call):
            # The cache files each fit under the profiling key it consumed
            # (the trace splits each base key and packs the first half),
            # the key `fitted_signatures` is handed.  The base keys give
            # the profiling keys' shape and dtype.
            with span("evaluate.fetch", call=call, leaves=1) as fetch_span:
                rows = np.asarray(packed)
                trips = jax.ShapeDtypeStruct(misfit.shape, jnp.int32)
                prof_keys_np, sigs_np, csigs_np, misfit_np, trips_np = _unpack_rows(
                    rows, (keys, sigs, csigs, misfit, trips)
                )
                fetch_span.set_metadata(fill_trips=int(trips_np.max()))
            cache_keys = _cache_keys(
                machine, wl_list, noise_std, background_bw, prof_keys_np
            )
            missing = [
                i for i, ck in enumerate(cache_keys) if _cache_lookup(ck) is None
            ]
            with span("evaluate.insert", call=call, misses=len(missing)):
                for i in missing:
                    _cache_insert(
                        cache_keys[i],
                        (
                            _tree_index(sigs_np, i),
                            _tree_index(csigs_np, i),
                            misfit_np[i],
                        ),
                    )
        return BatchAccuracy(
            placements=placements,
            errors_read=e_read,
            errors_write=e_write,
            errors_combined=e_comb,
            total_bw=totals,
            misfit=misfit,
            signatures=sigs,
            combined_signatures=csigs,
        )


# Numbers each evaluate_batch call, so that the spans of its phases name
# the call they belong to.
_CALLS = itertools.count()


def _tree_index(tree, i: int):
    return jax.tree.map(lambda x: x[i], tree)


def _accuracy_from_batch(batch: BatchAccuracy, i: int) -> AccuracyResult:
    return AccuracyResult(
        placements=batch.placements,
        errors_read=batch.errors_read[i],
        errors_write=batch.errors_write[i],
        errors_combined=batch.errors_combined[i],
        total_bw=batch.total_bw[i],
        misfit=batch.misfit[i],
        signature=_tree_index(batch.signatures, i),
    )


# ---------------------------------------------------------------------------
# Fitted-signature cache
# ---------------------------------------------------------------------------

_SIG_CACHE: dict[tuple, tuple[BandwidthSignature, BandwidthSignature, Array]] = {}
_SIG_CACHE_MAX = 4096
# One re-entrant lock serializes every _SIG_CACHE read-modify-write: the
# LRU touch (pop + re-insert) and the eviction sweep are multi-step dict
# mutations that interleave corruptly under free threading.  Fits are
# idempotent, so two threads racing on the same *miss* just both compute
# and the second insert wins — correctness never depends on the lock
# covering the (long) jitted fit itself.
_SIG_LOCK = threading.RLock()


def _workload_fingerprint(wl: Workload) -> tuple:
    """``(name, n_threads, digest of the array fields)``.  Memoized on the
    workload's identity (the value keeps the workload alive, so its id
    cannot be recycled) when every array field is a ``jax.Array``, which
    cannot change; a workload with NumPy fields can be changed in place,
    so it is digested anew on every call."""
    arrays = _workload_arrays(wl)
    immutable = all(isinstance(a, jax.Array) for a in arrays)
    if immutable:
        hit = _memo_get(_FINGERPRINT_CACHE, _MEMO_LOCK, id(wl))
        if hit is not None:
            return hit[1]
    digest = hashlib.blake2b(digest_size=16)
    for field in arrays:
        a = np.asarray(field)
        digest.update(str(a.shape).encode())
        digest.update(str(a.dtype).encode())
        digest.update(a.tobytes())
    fingerprint = (wl.name, wl.n_threads, digest.hexdigest())
    if immutable:
        _memo_put(
            _FINGERPRINT_CACHE, _MEMO_LOCK, id(wl), (wl, fingerprint),
            _MEMO_CACHE_MAX,
        )
    return fingerprint


_FINGERPRINT_CACHE: dict[int, tuple] = {}


def _cache_keys(machine, wl_list, noise_std, background_bw, keys) -> list[tuple]:
    """One signature-cache key per workload, ``keys`` its profiling keys.

    The machine is content-addressed through its fingerprint: topology
    tables (tuple-canonicalized from whatever array form they were built
    with) are digested alongside the scalar fields, so two specs with
    identical link matrices and routes share cache entries.  Per-node
    tuple spellings of core_rate / local_*_bw digest differently from
    their scalar equivalents, so a calibration-fitted machine never
    collides with the preset it was fitted from."""
    machine_fp = machine.fingerprint()
    keys = np.asarray(keys)
    return [
        (
            machine_fp,
            _workload_fingerprint(wl),
            float(noise_std),
            float(background_bw),
            keys[i].tobytes(),
        )
        for i, wl in enumerate(wl_list)
    ]


def _evict_cache_if_full() -> None:
    """Ordered FIFO/LRU eviction: drop the *oldest* entries (python dicts
    preserve insertion order; :func:`_cache_lookup` re-inserts on hit, so
    hot keys migrate to the young end and survive eviction cycles — the
    previous behaviour of clearing the whole cache at the high-water mark
    threw away every hot signature with the cold ones)."""
    with _SIG_LOCK:
        while len(_SIG_CACHE) > _SIG_CACHE_MAX:
            _SIG_CACHE.pop(next(iter(_SIG_CACHE)))


def _cache_lookup(cache_key: tuple):
    """LRU-touching get: a hit moves the entry to the young (newest) end
    (atomically — pop + re-insert under the cache lock)."""
    with _SIG_LOCK:
        value = _SIG_CACHE.pop(cache_key, None)
        if value is not None:
            _SIG_CACHE[cache_key] = value
        return value


def _cache_insert(cache_key: tuple, value) -> None:
    """Locked insert + eviction sweep (the only way entries enter the
    signature cache)."""
    with _SIG_LOCK:
        _SIG_CACHE[cache_key] = value
        while len(_SIG_CACHE) > _SIG_CACHE_MAX:
            _SIG_CACHE.pop(next(iter(_SIG_CACHE)))


@partial(
    jax.jit,
    static_argnames=("machine", "noise_std", "background_bw", "thread_classes"),
)
def _fit_batch_jit(
    machine, wl_arrays, prof_keys, noise_std, background_bw, thread_classes
):
    def per_benchmark(arrays, prof_key):
        return _fit_one(
            machine, arrays, prof_key, noise_std, background_bw, thread_classes
        )

    return jax.vmap(per_benchmark)(wl_arrays, prof_keys)


def fitted_signatures(
    machine: MachineSpec,
    workloads: Workload | Sequence[Workload],
    *,
    noise_std: float = 0.0,
    background_bw: float = 0.0,
    keys: Array | None = None,
) -> list[tuple[BandwidthSignature, BandwidthSignature, Array]]:
    """Cached 2-run fits: ``(signature, combined_signature, misfit)`` per
    workload.  ``keys`` are the *profiling* keys handed straight to
    ``profile_pair`` (the seed implementation's stream).  Cache key =
    (machine, workload, noise, key); misses are fitted in a single
    vmapped trace."""
    wl_list = _as_workload_list(workloads)
    keys = _normalize_keys(keys, len(wl_list))

    cache_keys = _cache_keys(machine, wl_list, noise_std, background_bw, keys)
    results = {}
    for i, ck in enumerate(cache_keys):
        hit = _cache_lookup(ck)
        if hit is not None:
            results[i] = hit
    missing = [i for i in range(len(wl_list)) if i not in results]
    if missing:
        missing_wls = [wl_list[i] for i in missing]
        stacked = _stack_workloads(missing_wls)
        sigs, csigs, mis = _fit_batch_jit(
            machine,
            stacked,
            keys[jnp.asarray(missing)],
            float(noise_std),
            float(background_bw),
            thread_class_starts(missing_wls),
        )
        for row, i in enumerate(missing):
            results[i] = (
                _tree_index(sigs, row),
                _tree_index(csigs, row),
                mis[row],
            )
            _cache_insert(cache_keys[i], results[i])
    return [results[i] for i in range(len(wl_list))]


# ---------------------------------------------------------------------------
# Paper §6 drivers
# ---------------------------------------------------------------------------


def evaluate_accuracy(
    machine: MachineSpec,
    workload: Workload,
    *,
    noise_std: float = 0.0,
    background_bw: float = 0.0,
    key: Array | None = None,
    max_placements: int | None = None,
) -> AccuracyResult:
    """Profile two placements, fit the bandwidth signature, and score its
    counter predictions against simulated measurements over the full
    placement sweep (§6.2: fit on 2 runs, predict the rest)."""
    if key is None:
        key = jax.random.PRNGKey(0)
    placements = sweep_placements(
        machine, workload.n_threads, max_placements=max_placements
    )
    batch = evaluate_batch(
        machine,
        [workload],
        placements,
        noise_std=noise_std,
        background_bw=background_bw,
        keys=jnp.stack([key]),
    )
    return _accuracy_from_batch(batch, 0)


def _default_suite_threads(machine: MachineSpec) -> int:
    """Largest single-socket thread count, rounded down so the symmetric
    profiling run can split it evenly over the machine's NUMA nodes (a
    no-op for every ``nodes_per_socket=1`` preset)."""
    n_threads = machine.cores_per_socket
    n_threads -= n_threads % machine.n_nodes
    return n_threads or machine.n_nodes


class SuiteAccuracy(NamedTuple):
    """Suite-level accuracy rollup: per-benchmark results plus the pooled
    error distribution and its headline percentiles."""

    names: list[str]
    per_benchmark: dict[str, AccuracyResult]
    all_errors: np.ndarray  # every counter measurement's % error
    median_error_pct: float
    p75_error_pct: float


def evaluate_suite(
    machine: MachineSpec,
    n_threads: int | None = None,
    *,
    noise_std: float = 0.0,
    include_violators: bool = True,
    seed: int = 0,
    max_placements: int | None = None,
) -> SuiteAccuracy:
    """Fit + predict every suite benchmark over every placement — the
    paper's "thousands of measurements" (§6.2.2) — in a single jitted
    ``evaluate_batch`` trace (no per-benchmark retracing)."""
    if n_threads is None:
        n_threads = _default_suite_threads(machine)
    names = suite_names(include_violators)
    key = jax.random.PRNGKey(seed)
    workloads = [benchmark_workload(name, n_threads) for name in names]
    keys = jnp.stack([jax.random.fold_in(key, i) for i in range(len(names))])
    placements = sweep_placements(machine, n_threads, max_placements=max_placements)
    batch = evaluate_batch(
        machine, workloads, placements, noise_std=noise_std, keys=keys
    )
    results = {
        name: _accuracy_from_batch(batch, i) for i, name in enumerate(names)
    }
    all_errors = np.asarray(batch.errors_combined).reshape(-1) * 100.0
    return SuiteAccuracy(
        names=names,
        per_benchmark=results,
        all_errors=all_errors,
        median_error_pct=float(np.median(all_errors)),
        p75_error_pct=float(np.percentile(all_errors, 75)),
    )


class StabilityResult(NamedTuple):
    """Signature stability across machines: how much each benchmark's
    fitted signature moves when refit on a different machine (§6.3)."""

    names: list[str]
    read_change: dict[str, float]
    write_change: dict[str, float]
    combined_change: dict[str, float]
    mean_combined_pct: float
    median_combined_pct: float


def evaluate_stability(
    machine_a: MachineSpec,
    machine_b: MachineSpec,
    n_threads_a: int | None = None,
    n_threads_b: int | None = None,
    *,
    noise_std: float = 0.0,
    include_violators: bool = True,
    seed: int = 0,
) -> StabilityResult:
    """Fit each benchmark on both machines; report reallocated bandwidth
    between the two signatures (paper Figures 13–15).  Each machine's
    suite is fitted through one batched (cached) trace."""
    if n_threads_a is None:
        n_threads_a = _default_suite_threads(machine_a)
    if n_threads_b is None:
        n_threads_b = _default_suite_threads(machine_b)
    names = suite_names(include_violators)
    key = jax.random.PRNGKey(seed)
    keys_a, keys_b = [], []
    for i in range(len(names)):
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        keys_a.append(ka)
        keys_b.append(kb)
    wl_a = [benchmark_workload(name, n_threads_a) for name in names]
    wl_b = [benchmark_workload(name, n_threads_b) for name in names]
    fits_a = fitted_signatures(
        machine_a, wl_a, noise_std=noise_std, keys=jnp.stack(keys_a)
    )
    fits_b = fitted_signatures(
        machine_b, wl_b, noise_std=noise_std, keys=jnp.stack(keys_b)
    )

    read_c, write_c, comb_c = {}, {}, {}
    for name, (sig_a, csig_a, _), (sig_b, csig_b, _) in zip(
        names, fits_a, fits_b
    ):
        read_c[name] = float(signature_distance(sig_a.read, sig_b.read)) * 100
        write_c[name] = float(signature_distance(sig_a.write, sig_b.write)) * 100
        comb_c[name] = float(signature_distance(csig_a.read, csig_b.read)) * 100
    vals = np.asarray(list(comb_c.values()))
    return StabilityResult(
        names=names,
        read_change=read_c,
        write_change=write_c,
        combined_change=comb_c,
        mean_combined_pct=float(vals.mean()),
        median_combined_pct=float(np.median(vals)),
    )
