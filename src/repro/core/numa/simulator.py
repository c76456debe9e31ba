"""Steady-state NUMA bandwidth simulator with max-min fair saturation.

Given a machine, a workload and a thread placement this computes the
execution rate of every thread under bandwidth saturation and emits the
performance counters the paper's fitting procedure reads.

Placements are vectors of thread counts per NUMA *node* (for
``nodes_per_socket=1`` machines a node is a socket, the paper's case).
Each thread issues at its node's ``core_rate`` — heterogeneous machines
(throttled sockets, big.LITTLE) make threads on slow nodes demand
proportionally less bandwidth and retire fewer instructions.

The saturation model is *progressive filling* (max-min fairness): all
threads speed up together until some resource (a memory bank's read or
write capacity, a remote path, the interconnect, or the core issue rate)
saturates; the threads crossing that resource freeze and the rest keep
growing.  This reproduces the first-order behaviour the paper observes —
e.g. a single thread saturating the QPI on the low-end machine (§5.2) and
the rate asymmetries between sockets that motivate the normalization step.

The solver is a fixed-iteration ``lax.fori_loop`` and the whole function is
``jit``/``vmap``-able over placements, so evaluating thousands of
placements (paper §6.2.2: 2322 data points) is a single batched call.
Interconnect structure (link list, routes, the pair→link incidence
matrices consumed below) comes from the machine's topology — a
:mod:`repro.core.graphtop` link graph — and enters the trace as
compile-time constants.

Group-collapsed hot path
------------------------

Threads on the same NUMA node with the same per-thread workload column
(mix fractions + bytes/instruction) are *identical* rows of the resource
slab, so the solver never needs the thread axis: :func:`simulate` runs
max-min fairness over **thread groups** — ``(class, node)`` equivalence
classes with integer multiplicities — shrinking the slab from
``(n_threads, R)`` to ``(n_classes * n_nodes, R)`` (32 -> 8 rows on the
8-socket preset for a homogeneous workload) and the iteration bound from
``min(n_threads, R) + 1`` to ``min(n_groups, R) + 1``.  Classes are
*static* maximal runs of the thread index range over which every
workload array is constant (:func:`thread_class_starts`); group
multiplicities are cheap traced interval overlaps, so the grouped path
stays ``jit``/``vmap``-able over placements and differentiable through
``caps``.  Per-thread rates, flows and counters are reconstructed
exactly from the group rates (identical support rows freeze together in
progressive filling, so members of a group provably share one rate).

:func:`simulate_reference` keeps the per-thread formulation verbatim as
the test-only reference implementation (the way PR 3's verbatim replica
pinned the node refactor); when ``simulate`` cannot learn the class
structure (traced workload arrays and no ``thread_classes`` argument) it
falls back to that path.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.bwsig.counters import CounterSample, counters_from_flows
from repro.core.numa.machine import MachineSpec, canonical_bank_assignment
from repro.core.numa.workload import Workload

_EPS = 1e-12
# Every f32 contraction of the solver runs at full f32 precision.  A TPU's
# default matmul precision rounds f32 operands to bf16, far coarser than
# the fill's 1e-6 bottleneck tie tolerance: ties would flip, and with them
# which groups freeze and every rate downstream.
_HI = jax.lax.Precision.HIGHEST
# exp's Taylor coefficients 1/k!, k = 7 down to 0, for Horner's rule, and
# the range of exponents it serves (see :func:`noise_factor`)
_EXP_SERIES = tuple(1.0 / math.factorial(k) for k in range(7, -1, -1))
_EXP_SERIES_RANGE = 0.25


def noise_factor(x: Array) -> Array:
    """``exp(x)``, within a few float32 ulp of the exact value on every
    backend: the lognormal measurement-noise factor ``exp(noise_std * z)``.

    A TPU's ``jnp.exp`` reads about 1.2e-06 low, ten to twenty float32
    ulp, which shifts every noised flow alike.  For ``|x| <= 0.25`` (``z`` within 12
    standard deviations at 2% noise) the degree-7 Taylor polynomial in
    Horner form is used instead: its truncation is below 1e-09 relative
    there and its float32 rounding about an ulp.  Beyond that range
    ``jnp.exp`` is kept."""
    p = jnp.full_like(x, _EXP_SERIES[0])
    for c in _EXP_SERIES[1:]:
        p = p * x + c
    return jnp.where(jnp.abs(x) <= _EXP_SERIES_RANGE, p, jnp.exp(x))


class SimulationResult(NamedTuple):
    """One simulated run: per-thread rates, per-node-pair flow matrices,
    the counter sample the model is allowed to observe, and the scalar
    throughput the sweep/search layers maximize."""

    rates: Array  # (n,) per-thread execution-rate multiplier in (0, 1]
    read_flows: Array  # (n_nodes, n_nodes) bytes/s from node i CPUs to bank j
    write_flows: Array  # (n_nodes, n_nodes)
    sample: CounterSample  # the counters the model is allowed to see
    throughput: Array  # scalar: sum of thread rates (relative performance)


def _thread_nodes(n_per_node: Array, n_threads: int) -> Array:
    """Contiguous thread->node assignment: the first ``n_0`` threads land
    on node 0, the next ``n_1`` on node 1, ...  (This ordering is what
    makes the Page-rank violator's early-chunk threads move between nodes
    as the placement changes.)"""
    bounds = jnp.cumsum(n_per_node)
    t = jnp.arange(n_threads)
    return jnp.searchsorted(bounds, t, side="right").astype(jnp.int32)


def _mix_rows(
    static_frac: Array,
    local_frac: Array,
    per_thread_frac: Array,
    static_socket: Array,
    node_of: Array,
    n_per_node: Array,
    bank_assignment: tuple[int, ...] | None = None,
) -> Array:
    """Ground-truth per-thread traffic mix over banks — the per-thread
    version of the paper's §4 class matrices.  One bank per NUMA node;
    ``static_socket`` names the *node* holding the Static allocation.
    ``bank_assignment`` redirects the Local class: a thread on node ``k``
    reads its "local" buffers from bank ``bank_assignment[k]`` (pages left
    behind by a migration, or deliberately placed on another node)."""
    s = n_per_node.shape[0]
    n = node_of.shape[0]
    nf = n_per_node.astype(jnp.float32)
    used = (nf > 0).astype(jnp.float32)
    s_used = jnp.maximum(used.sum(), 1.0)

    static_row = (jnp.arange(s) == static_socket).astype(jnp.float32)  # (s,)
    if bank_assignment is None:
        local_rows = jax.nn.one_hot(node_of, s)  # (n, s)
    else:
        bank_of = jnp.asarray(bank_assignment, jnp.int32)[node_of]
        local_rows = jax.nn.one_hot(bank_of, s)  # (n, s)
    pt_row = nf / jnp.maximum(nf.sum(), 1.0)  # (s,)
    il_row = used / s_used  # (s,)

    inter = 1.0 - static_frac - local_frac - per_thread_frac
    mix = (
        static_frac[:, None] * static_row[None, :]
        + local_frac[:, None] * local_rows
        + per_thread_frac[:, None] * pt_row[None, :]
        + inter[:, None] * il_row[None, :]
    )
    return mix  # (n, s)


def machine_caps(machine: MachineSpec) -> Array:
    """The capacity vector of :func:`_resource_tensor`'s resource slab, in
    slab order: bank reads (s), bank writes (s), remote read paths (s*s),
    remote write paths (s*s), interconnect links (n_links).  Split out so
    the calibration inverse problem can substitute a *traced* capacity
    vector (free parameters under ``jax.grad``) while the machine itself
    stays the static structural template."""
    s = machine.n_nodes
    return jnp.concatenate(
        [
            machine.bank_read_caps(),
            machine.bank_write_caps(),
            machine.remote_read_caps().reshape(s * s),
            machine.remote_write_caps().reshape(s * s),
            machine.link_caps(),
        ]
    )


def _resource_tensor(
    machine: MachineSpec,
    read_unit: Array,  # (n, s) bytes/s to each bank at full speed
    write_unit: Array,  # (n, s)
    node_of: Array,  # (n,)
    caps: Array | None = None,  # capacity-vector override (calibration)
    multipath: bool = False,
) -> tuple[Array, Array]:
    """Build the per-thread resource-usage matrix ``U[t, r]`` and the
    capacity vector ``caps[r]``.

    With ``s = machine.n_nodes`` (one bank per NUMA node), resources are:
    bank read caps (s), bank write caps (s), remote read paths (s*s,
    diagonal unconstrained, per-pair hop-attenuated capacity), remote
    write paths (s*s), interconnect *links* (n_links): a flow from node
    ``i`` to bank ``j`` charges every link on ``route(i, j)``.

    The routing structure is static (python tuples on the machine), so the
    link slab keeps a fixed ``(n, n_links)`` shape that jit and vmap handle
    identically for any node count or topology.  ``caps`` overrides the
    machine-derived capacity vector (same slab order, from
    :func:`machine_caps`) — the hook the calibration fit differentiates
    through.

    ``multipath=True`` splits each pair's flow evenly over all of its
    equal-cost widest routes (``graphtop`` fractional incidence) instead
    of charging the single primary route; the default single-route
    charging is unchanged bit for bit.
    """
    s = machine.n_nodes
    n = node_of.shape[0]
    topo = machine.topology
    onehot = jax.nn.one_hot(node_of, s)  # (n, s)

    # (n, s, s): thread t's flow from its node i to bank j.
    rr = onehot[:, :, None] * read_unit[:, None, :]
    ww = onehot[:, :, None] * write_unit[:, None, :]
    off_diag = (1.0 - jnp.eye(s))[None, :, :]
    rr_remote = rr * off_diag
    ww_remote = ww * off_diag

    # Per-link usage, in two parts.  (1) Direct traffic: each link always
    # carries its own endpoint pair (both directions) — a vectorized
    # endpoint-index gather summed in the scalar-pair model's exact order,
    # so fully-connected topologies reproduce it bit for bit.  (2) Routed
    # traffic: multi-hop pairs charge the full flow to every link on their
    # route via the static pair->link incidence matrix.  Under multipath
    # the two-part split is meaningless (a "direct" pair may still split
    # over parallel equal-cost routes), so the whole charge goes through
    # the fractional incidence in one matmul.
    n_links = topo.n_links
    if n_links and multipath:
        inc = jnp.asarray(topo.route_incidence(multipath=True))  # (s*s, L)
        link_usage = jnp.matmul(
            (rr_remote + ww_remote).reshape(n, s * s), inc, precision=_HI
        )
    elif n_links:
        ends_i = np.asarray([e[0] for e in topo.link_ends])
        ends_j = np.asarray([e[1] for e in topo.link_ends])
        link_usage = (
            rr_remote[:, ends_i, ends_j]
            + rr_remote[:, ends_j, ends_i]
            + ww_remote[:, ends_i, ends_j]
            + ww_remote[:, ends_j, ends_i]
        )
        if not topo.is_fully_direct:
            routed = jnp.asarray(topo.route_incidence_multihop())  # (s*s, L)
            cross = (rr_remote + ww_remote).reshape(n, s * s)
            link_usage = link_usage + jnp.matmul(cross, routed, precision=_HI)
    else:
        link_usage = jnp.zeros((n, 0))

    usage = jnp.concatenate(
        [
            read_unit,  # bank read
            write_unit,  # bank write
            rr_remote.reshape(n, s * s),
            ww_remote.reshape(n, s * s),
            link_usage,
        ],
        axis=1,
    )

    if caps is None:
        caps = machine_caps(machine)
    return usage, caps


def _progressive_fill(usage: Array, caps: Array, iterations: int) -> Array:
    """Max-min fair rates: grow all threads together, freeze the set
    crossing each successive bottleneck."""
    n = usage.shape[0]

    def body(_, state):
        x, frozen = state
        active = ~frozen
        frozen_usage = (usage * jnp.where(frozen, x, 0.0)[:, None]).sum(0)
        act_usage = (usage * active[:, None].astype(usage.dtype)).sum(0)
        resid = jnp.maximum(caps - frozen_usage, 0.0)
        lam = jnp.where(act_usage > _EPS, resid / jnp.maximum(act_usage, _EPS), jnp.inf)
        lam_star = jnp.minimum(jnp.min(lam), 1.0)
        bottleneck = lam <= lam_star * (1.0 + 1e-6)
        uses_bottleneck = (usage * bottleneck[None, :]).sum(1) > _EPS
        freeze_now = active & (uses_bottleneck | (lam_star >= 1.0))
        x = jnp.where(freeze_now, lam_star, x)
        frozen = frozen | freeze_now
        return x, frozen

    x0 = jnp.zeros((n,), usage.dtype)
    frozen0 = jnp.zeros((n,), bool)
    x, frozen = jax.lax.fori_loop(0, iterations, body, (x0, frozen0))
    # Anything still unfrozen touches no finite resource: runs at full speed.
    return jnp.where(frozen, x, 1.0)


def simulate_reference(
    machine: MachineSpec,
    workload: Workload,
    n_per_node: Array,
    *,
    elapsed: float = 1.0,
    noise_std: float = 0.0,
    background_bw: float = 0.0,
    key: Array | None = None,
    caps: Array | None = None,
    multipath: bool = False,
    bank_assignment: tuple[int, ...] | None = None,
) -> SimulationResult:
    """The per-thread reference solver: one resource-slab row per thread.

    This is the pre-grouping formulation kept verbatim — the reference
    implementation the grouped hot path (:func:`simulate`) is tested
    against, and the fallback when the class structure of a traced
    workload is unknown.  Prefer :func:`simulate` everywhere else: it is
    exact to ~1 ulp and its cost scales with nodes, not threads."""
    bank_assignment = canonical_bank_assignment(machine, bank_assignment)
    s = machine.n_nodes
    n = workload.n_threads
    n_per_node = jnp.asarray(n_per_node)
    node_of = _thread_nodes(n_per_node, n)
    rate_of = machine.node_rates()[node_of]  # (n,) per-thread issue rate

    read_mix = _mix_rows(
        workload.read_static,
        workload.read_local,
        workload.read_per_thread,
        workload.static_socket,
        node_of,
        n_per_node,
        bank_assignment,
    )
    write_mix = _mix_rows(
        workload.write_static,
        workload.write_local,
        workload.write_per_thread,
        workload.static_socket,
        node_of,
        n_per_node,
        bank_assignment,
    )
    read_unit = rate_of[:, None] * workload.read_bpi[:, None] * read_mix
    write_unit = rate_of[:, None] * workload.write_bpi[:, None] * write_mix

    usage, caps = _resource_tensor(
        machine, read_unit, write_unit, node_of, caps, multipath=multipath
    )
    # Each progressive-filling iteration freezes at least one thread set
    # (either a bottleneck's users or, at lam* >= 1, every active thread),
    # and each bottleneck saturates at most one new resource — so
    # min(n_threads, n_resources) + 1 iterations always reach the fixed
    # point.  (The former n_resources + 2 count was 172 iterations on the
    # 8-socket preset for 32 threads.)
    iterations = min(usage.shape[0], usage.shape[1]) + 1
    rates = _progressive_fill(usage, caps, iterations)

    onehot = jax.nn.one_hot(node_of, s)
    read_flows = jnp.matmul(
        onehot.T, rates[:, None] * read_unit, precision=_HI
    ) * elapsed
    write_flows = jnp.matmul(
        onehot.T, rates[:, None] * write_unit, precision=_HI
    ) * elapsed
    instructions = jnp.matmul(onehot.T, rates * rate_of, precision=_HI) * elapsed

    return _finalize_result(
        rates, read_flows, write_flows, instructions, n_per_node,
        elapsed, noise_std, background_bw, key, s,
    )


def _finalize_result(
    rates: Array,
    read_flows: Array,
    write_flows: Array,
    instructions: Array,
    n_per_node: Array,
    elapsed: float,
    noise_std: float,
    background_bw: float,
    key: Array | None,
    s: int,
) -> SimulationResult:
    """Measurement noise + counter reduction, shared by the grouped and
    per-thread paths (op-for-op the pre-grouping tail of ``simulate``)."""
    if noise_std > 0.0 or background_bw > 0.0:
        if key is None:
            key = jax.random.PRNGKey(0)
        k1, k2, k3 = jax.random.split(key, 3)
        read_flows = read_flows * noise_factor(
            noise_std * jax.random.normal(k1, read_flows.shape)
        ) + background_bw * elapsed / (s * s)
        write_flows = write_flows * noise_factor(
            noise_std * jax.random.normal(k2, write_flows.shape)
        ) + background_bw * elapsed / (s * s)
        instructions = instructions * noise_factor(
            0.2 * noise_std * jax.random.normal(k3, instructions.shape)
        )

    sample = counters_from_flows(
        read_flows, write_flows, instructions, jnp.asarray(elapsed), n_per_node
    )
    return SimulationResult(
        rates=rates,
        read_flows=read_flows,
        write_flows=write_flows,
        sample=sample,
        throughput=rates.sum(),
    )


# ---------------------------------------------------------------------------
# Group-collapsed solver: (class, node) equivalence classes of threads
# ---------------------------------------------------------------------------


def class_starts_from_arrays(arrays) -> tuple[int, ...]:
    """Static thread-class boundaries from concrete per-thread arrays.

    Classes are *maximal runs* of the thread index range over which every
    array (last axis = threads; scalars are skipped) is constant.  Runs —
    not value-equivalence classes — because the contiguous thread->node
    assignment makes interval overlap the multiplicity computation; a
    finer partition is always correct.  Returns the tuple of class start
    indices, e.g. ``(0,)`` for a homogeneous workload or ``(0, n//2)``
    for the Page-rank violator's hot/cold halves."""
    boundary = None
    for a in arrays:
        a = np.asarray(a)
        if a.ndim == 0 or a.shape[-1] < 2:
            continue
        diff = a[..., 1:] != a[..., :-1]
        diff = diff.reshape(-1, diff.shape[-1]).any(axis=0)
        boundary = diff if boundary is None else (boundary | diff)
    if boundary is None:
        return (0,)
    return (0,) + tuple(int(i) + 1 for i in np.flatnonzero(boundary))


def thread_class_starts(workloads) -> tuple[int, ...]:
    """Common static class refinement over one or more workloads: the
    partition of ``[0, n)`` into maximal runs where *every* workload's
    per-thread arrays are constant.  A batch of workloads evaluated in
    one trace must share one (static) partition, so the refinement is the
    union of each workload's class boundaries."""
    if isinstance(workloads, Workload):
        workloads = [workloads]
    # wl[1:-1]: every per-thread array field; static_socket (a scalar, and
    # a per-*sample* axis once stacked) never partitions the thread range.
    arrays = [a for wl in workloads for a in wl[1:-1]]
    return class_starts_from_arrays(arrays)


def _infer_thread_classes(workload: Workload) -> tuple[int, ...] | None:
    """Class boundaries from a concrete workload; ``None`` when any array
    field is traced (inside jit/vmap the values are unreadable — callers
    must pass ``thread_classes`` explicitly to stay on the grouped path)."""
    if any(isinstance(f, jax.core.Tracer) for f in workload[1:]):
        return None
    return thread_class_starts(workload)


def _group_multiplicities(
    class_starts: tuple[int, ...], n: int, n_per_node: Array
) -> Array:
    """``(C, s)`` thread count of class ``c`` on node ``k``: the overlap
    of the static class interval with the traced node interval of the
    contiguous thread->node assignment.  ``n_per_node`` may carry trailing
    placement axes, ``(s, ...)``; the result then has them too,
    ``(C, s, ...)``."""
    bounds = jnp.asarray(class_starts + (n,), jnp.int32)  # (C+1,) static
    bounds = bounds.reshape(bounds.shape + (1,) * n_per_node.ndim)
    node_hi = jnp.cumsum(n_per_node.astype(jnp.int32), axis=0)
    node_lo = node_hi - n_per_node.astype(jnp.int32)
    lo = jnp.maximum(bounds[:-1], node_lo[None])
    hi = jnp.minimum(bounds[1:], node_hi[None])
    return jnp.maximum(hi - lo, 0)


def _group_mix_rows(
    static_frac: Array,  # (C,)
    local_frac: Array,
    per_thread_frac: Array,
    static_socket: Array,
    n_per_node: Array,
    bank_assignment: tuple[int, ...] | None = None,
) -> Array:
    """``(C, s, s)`` traffic mix over banks for a class-``c`` thread
    placed on node ``k`` — :func:`_mix_rows` with the thread axis replaced
    by the (class, node) grid.  ``bank_assignment`` redirects row ``k``'s
    Local column to bank ``bank_assignment[k]`` (see
    :func:`repro.core.numa.machine.canonical_bank_assignment`)."""
    s = n_per_node.shape[0]
    nf = n_per_node.astype(jnp.float32)
    used = (nf > 0).astype(jnp.float32)
    s_used = jnp.maximum(used.sum(), 1.0)

    static_row = (jnp.arange(s) == static_socket).astype(jnp.float32)  # (s,)
    if bank_assignment is None:
        local_rows = jnp.eye(s)  # node k's local row
    else:
        local_rows = jax.nn.one_hot(jnp.asarray(bank_assignment, jnp.int32), s)
    pt_row = nf / jnp.maximum(nf.sum(), 1.0)
    il_row = used / s_used

    inter = 1.0 - static_frac - local_frac - per_thread_frac
    return (
        static_frac[:, None, None] * static_row[None, None, :]
        + local_frac[:, None, None] * local_rows[None, :, :]
        + per_thread_frac[:, None, None] * pt_row[None, None, :]
        + inter[:, None, None] * il_row[None, None, :]
    )


def _group_resource_tensor(
    machine: MachineSpec,
    read_unit: Array,  # (C, s, s) bytes/s of one class-c thread on node k
    write_unit: Array,
    caps: Array | None = None,
    multipath: bool = False,
) -> tuple[Array, Array]:
    """Per-*group* resource-usage matrix ``U[g, r]`` (``g = c * s + k``)
    in the exact slab order of :func:`_resource_tensor` / :func:`machine_caps`.

    Each group only ever occupies its own node's row of the ``s x s``
    remote slabs, so those columns are built by a static scatter (every
    group row places its ``s`` bank flows at columns ``k*s + j``) instead
    of the per-thread path's dense one-hot masking; per-link charges
    gather the node's rows of the full route-incidence matrix (direct and
    multi-hop routes alike, matching the reference's two-part sum).
    ``multipath=True`` swaps in the fractional equal-cost-multipath
    incidence (bit-for-bit unchanged when off)."""
    s = machine.n_nodes
    C = read_unit.shape[0]
    G = C * s
    topo = machine.topology

    read_flat = read_unit.reshape(G, s)
    write_flat = write_unit.reshape(G, s)
    node_idx = np.tile(np.arange(s), C)  # (G,) static: group g lives on node g%s
    offdiag = jnp.asarray(
        np.arange(s)[None, :] != node_idx[:, None], read_flat.dtype
    )  # (G, s) static constant
    rr_vals = read_flat * offdiag
    ww_vals = write_flat * offdiag

    cols = node_idx[:, None] * s + np.arange(s)[None, :]  # (G, s) static
    rows = np.arange(G)[:, None]
    rr_remote = jnp.zeros((G, s * s), read_flat.dtype).at[rows, cols].set(rr_vals)
    ww_remote = jnp.zeros((G, s * s), write_flat.dtype).at[rows, cols].set(ww_vals)

    if topo.n_links:
        # (s, s, L) static: node k's rows of the full pair->link incidence
        inc = np.asarray(
            topo.route_incidence(multipath=multipath)
        ).reshape(s, s, topo.n_links)
        inc_rows = jnp.asarray(inc[node_idx])  # (G, s, L) static constant
        link_usage = jnp.einsum(
            "gj,gjl->gl", rr_vals + ww_vals, inc_rows, precision=_HI
        )
    else:
        link_usage = jnp.zeros((G, 0))

    usage = jnp.concatenate(
        [read_flat, write_flat, rr_remote, ww_remote, link_usage], axis=1
    )
    if caps is None:
        caps = machine_caps(machine)
    return usage, caps


def _progressive_fill_grouped(
    unit_usage: Array, mult: Array, caps: Array, iterations: int
) -> Array:
    """Weighted max-min fairness over thread groups: ``unit_usage[g]`` is
    one member's resource row, ``mult[g]`` the member count.  Identical
    rows freeze together in :func:`_progressive_fill` (the freeze rule
    only reads a row's *support*), so solving over groups with summed
    usage reproduces the per-thread rates exactly; empty groups carry
    zero usage and cannot move any bottleneck."""
    g = unit_usage.shape[0]
    total_usage = unit_usage * mult[:, None]

    def body(_, state):
        x, frozen = state
        active = ~frozen
        frozen_usage = (total_usage * jnp.where(frozen, x, 0.0)[:, None]).sum(0)
        act_usage = (total_usage * active[:, None].astype(unit_usage.dtype)).sum(0)
        resid = jnp.maximum(caps - frozen_usage, 0.0)
        lam = jnp.where(act_usage > _EPS, resid / jnp.maximum(act_usage, _EPS), jnp.inf)
        lam_star = jnp.minimum(jnp.min(lam), 1.0)
        bottleneck = lam <= lam_star * (1.0 + 1e-6)
        uses_bottleneck = (unit_usage * bottleneck[None, :]).sum(1) > _EPS
        freeze_now = active & (uses_bottleneck | (lam_star >= 1.0))
        x = jnp.where(freeze_now, lam_star, x)
        frozen = frozen | freeze_now
        return x, frozen

    x0 = jnp.zeros((g,), unit_usage.dtype)
    frozen0 = jnp.zeros((g,), bool)
    x, frozen = jax.lax.fori_loop(0, iterations, body, (x0, frozen0))
    return jnp.where(frozen, x, 1.0)


# ---------------------------------------------------------------------------
# Batched shared-slab evaluation: one resource build per support bucket
# ---------------------------------------------------------------------------
#
# A placement enters the grouped solver through exactly three channels:
# the (C, s) multiplicity grid, the per-thread row ``pt_row = n / sum(n)``
# and the interleave row ``il_row = used / s_used`` (support only).  The
# unit-demand tensor is *linear* in the mix rows, so it decomposes exactly:
#
#   unit(c, k, j) = base(c, k, j)                      static + local terms
#                 + pt_coeff(c, k) * pt_row(j)         per-thread term
#                 + il_coeff(c, k) * il_row(j)         interleaved term
#
# ``base`` and the coefficients are placement-independent (built once per
# benchmark); ``il_row`` only depends on the placement's *support pattern*
# (which nodes hold any thread), so placements are bucketed by support and
# the base+interleave slab — including its per-link charges — is built
# once per bucket.  Only the rank-1 ``pt_row`` update and the multiplicity
# grid remain per-placement work.
#
# The slab itself is kept *structured* instead of materializing the dense
# ``(G, R)`` matrix of :func:`_group_resource_tensor`: each remote path
# ``(k, j)`` is used only by the C groups living on node ``k``, so the
# remote constraints stay in ``(C, s, s)`` form (``2*C*s^2`` entries
# instead of the dense scatter's ``2*C*s^3``), and one sum over the
# classes gives both the bank loads and the remote-path loads.  The
# max-min semantics are identical; only the zero padding is gone.
#
# The batch keeps the placements on the *last* axis of every array.  On a
# TPU the two minor axes are stored in (8, 128) tiles, so a per-placement
# ``(s, s)`` block as the minor axes would be padded to ``(8, 128)``; with
# the placements minor, the lanes are full and the small axes are rows.


class GroupSlabs(NamedTuple):
    """Placement-independent slab components of one benchmark's unit
    demand (see the decomposition note above)."""

    base_read: Array  # (C, s, s) static + local unit demand
    base_write: Array  # (C, s, s)
    pt_read: Array  # (C, s) coefficient of the per-thread row
    pt_write: Array  # (C, s)
    il_read: Array  # (C, s) coefficient of the interleave row
    il_write: Array  # (C, s)


class GroupedBatchResult(NamedTuple):
    """Per-placement ground truth from :func:`simulate_grouped_batch`
    (noise-free; measurement noise is a batched post-pass for the callers
    that want it)."""

    read_flows: Array  # (P, s, s)
    write_flows: Array  # (P, s, s)
    instructions: Array  # (P, s)
    throughput: Array  # (P,) sum of thread rates
    group_rates: Array  # (P, C, s) shared rate of class c on node k
    fill_trips: Array  # (P,) int32 trips the fill took for each placement


def group_slab_components(
    machine: MachineSpec,
    workload: Workload,
    thread_classes: tuple[int, ...],
    bank_assignment: tuple[int, ...] | None = None,
) -> GroupSlabs:
    """Build the placement-independent unit-demand components for every
    (class, node) group — one call per benchmark, shared by every
    placement bucket.  ``bank_assignment`` (canonicalized: ``None`` means
    node-local) lands in the Local term of the base slab, so the whole
    batched path — including :func:`_group_resource_tensor`-style route
    charging of now-remote Local flows — prices page placement with zero
    extra per-placement work."""
    s = machine.n_nodes
    rep = np.asarray(thread_classes, np.int64)  # class representatives
    node_rates = machine.node_rates()  # (s,)
    if bank_assignment is None:
        local_mat = jnp.eye(s, dtype=node_rates.dtype)
    else:
        local_mat = jax.nn.one_hot(
            jnp.asarray(bank_assignment, jnp.int32), s, dtype=node_rates.dtype
        )

    def direction(static_frac, local_frac, pt_frac, bpi):
        sf = static_frac[rep]
        lf = local_frac[rep]
        pf = pt_frac[rep]
        inter = 1.0 - sf - lf - pf
        unit = node_rates[None, :, None] * bpi[rep][:, None, None]  # (C, s, 1)
        static_row = (
            jnp.arange(s) == workload.static_socket
        ).astype(node_rates.dtype)
        base = unit * (
            sf[:, None, None] * static_row[None, None, :]
            + lf[:, None, None] * local_mat[None, :, :]
        )
        coeff = unit[:, :, 0]  # (C, s)
        return base, coeff * pf[:, None], coeff * inter[:, None]

    base_r, pt_r, il_r = direction(
        workload.read_static,
        workload.read_local,
        workload.read_per_thread,
        workload.read_bpi,
    )
    base_w, pt_w, il_w = direction(
        workload.write_static,
        workload.write_local,
        workload.write_per_thread,
        workload.write_bpi,
    )
    return GroupSlabs(base_r, base_w, pt_r, pt_w, il_r, il_w)


def split_caps(
    machine: MachineSpec, caps: Array | None = None
) -> tuple[Array, Array, Array]:
    """Split a :func:`machine_caps`-order capacity vector into the
    structured fill's three blocks: dense ``[bank reads (s), bank writes
    (s), links (L)]``, remote-read ``(s, s)`` and remote-write ``(s, s)``."""
    s = machine.n_nodes
    if caps is None:
        dense = jnp.concatenate(
            [machine.bank_read_caps(), machine.bank_write_caps(), machine.link_caps()]
        )
        return dense, machine.remote_read_caps(), machine.remote_write_caps()
    dense = jnp.concatenate([caps[: 2 * s], caps[2 * s + 2 * s * s :]])
    rr = caps[2 * s : 2 * s + s * s].reshape(s, s)
    ww = caps[2 * s + s * s : 2 * s + 2 * s * s].reshape(s, s)
    return dense, rr, ww


def _progressive_fill_structured(
    ru: Array,  # (C, s, s, P) unit read demand of a class-c thread on node k, by bank
    wu: Array,  # (C, s, s, P) unit write demand
    lu: Array,  # (C, s, L, P) unit charge on each interconnect link
    mult: Array,  # (C, s, P) group multiplicities (float)
    dense_caps: Array,  # (2s + L,) bank reads, bank writes, links
    rr_caps: Array,  # (s, s) inf diagonal
    ww_caps: Array,  # (s, s)
    iterations: int,
    early_exit: bool = False,
) -> tuple[Array, Array]:
    """:func:`_progressive_fill_grouped` over the structured slab of a
    whole placement batch, the placements on the trailing axis ``P``.

    Each remote path ``(k, j)`` is used only by the groups on node ``k``,
    so one sum over the classes gives every node's flow toward every bank;
    the bank loads are its sums over nodes and the remote paths its
    off-diagonal entries.  Every operand keeps ``P`` as its last axis, so
    a lane of the device holds one placement and nothing pads the small
    per-group matrices.  Same freeze rule, bottleneck tolerance and fixed
    point, lane by lane.

    ``early_exit=True`` swaps the fori_loop for a while_loop that runs
    until every group of every placement froze.  A trip over a placement
    whose groups all froze changes nothing (no active group: ``lam*`` is
    1 and nothing freezes), so each placement's rates are those of a loop
    run for it alone; the while_loop is not reverse-differentiable, so
    the gradient paths keep the fixed-count loop.  Call with ``P = 1``
    (and take ``[..., 0]``) to fill one placement.

    Returns the ``(C, s, P)`` rates and the ``(P,)`` int32 trips each
    placement took: those in which it still had an active group under
    the while_loop, ``iterations`` under the fixed-count loop."""
    C, s, _, P = ru.shape
    dtype = ru.dtype
    offdiag = (1.0 - jnp.eye(s, dtype=dtype))[:, :, None]  # (s, s, 1)
    br_caps = dense_caps[:s, None]
    bw_caps = dense_caps[s : 2 * s, None]
    link_caps = dense_caps[2 * s :, None]
    rr_caps = rr_caps[:, :, None]
    ww_caps = ww_caps[:, :, None]

    def lam_of(caps, used, act):
        resid = jnp.maximum(caps - used, 0.0)
        return jnp.where(act > _EPS, resid / jnp.maximum(act, _EPS), jnp.inf)

    def body(state):
        x, frozen, trips = state
        active = ~frozen
        # the frozen groups' rates and the active groups' unit weights,
        # stacked so that one pass over each slab block serves both
        w = jnp.stack(
            [jnp.where(frozen, x, 0.0) * mult, jnp.where(active, mult, 0.0)]
        )[:, :, :, None]  # (2, C, s, 1, P)
        # (s, s, P): node k's flow toward bank j; (L, P): each link's load
        fz_r, act_r = (w * ru).sum(1)
        fz_w, act_w = (w * wu).sum(1)
        fz_l, act_l = (w * lu).sum((1, 2))
        lam_br = lam_of(br_caps, fz_r.sum(0), act_r.sum(0))  # (s, P)
        lam_bw = lam_of(bw_caps, fz_w.sum(0), act_w.sum(0))
        lam_l = lam_of(link_caps, fz_l, act_l)  # (L, P)
        lam_rr = lam_of(rr_caps, fz_r * offdiag, act_r * offdiag)  # (s, s, P)
        lam_ww = lam_of(ww_caps, fz_w * offdiag, act_w * offdiag)
        lam_star = jnp.minimum(
            jnp.minimum(
                jnp.minimum(lam_br.min(0), lam_bw.min(0)),
                jnp.minimum(lam_rr.min((0, 1)), lam_ww.min((0, 1))),
            ),
            jnp.minimum(lam_l.min(0, initial=jnp.inf), 1.0),
        )  # (P,)
        tol = lam_star * (1.0 + 1e-6)

        def bottleneck(lam):
            return (lam <= tol).astype(dtype)

        # bottlenecks a unit of flow from node k to bank j crosses: the
        # bank and the remote path (never the diagonal, whose lam is inf)
        hit_r = bottleneck(lam_br)[None] + bottleneck(lam_rr)  # (s, s, P)
        hit_w = bottleneck(lam_bw)[None] + bottleneck(lam_ww)
        uses = (
            (ru * hit_r).sum(2) + (wu * hit_w).sum(2)
            + (lu * bottleneck(lam_l)).sum(2)
        ) > _EPS  # (C, s, P)
        freeze_now = active & (uses | (lam_star >= 1.0))
        x = jnp.where(freeze_now, lam_star, x)
        return x, frozen | freeze_now, trips + active.any((0, 1))

    state0 = (
        jnp.zeros((C, s, P), dtype),
        jnp.zeros((C, s, P), bool),
        jnp.zeros((P,), jnp.int32),
    )
    if early_exit:
        x, frozen, trips = jax.lax.while_loop(
            lambda st: ~jnp.all(st[1]), body, state0
        )
    else:
        x, frozen, _ = jax.lax.fori_loop(
            0, iterations, lambda _, st: body(st), state0
        )
        trips = jnp.full((P,), iterations, jnp.int32)
    return jnp.where(frozen, x, 1.0), trips


def bucket_size(n: int, *, base: int = 8) -> int:
    """The padded batch size for ``n`` rows: the smallest power-of-two
    bucket >= ``base`` that holds them.  Variable-size batches (search
    leaf batches, the advisor service's micro-batches) jit one trace per
    *bucket* instead of one per exact size, so steady-state serving stops
    retracing as soon as every bucket has been seen once."""
    if n < 0:
        raise ValueError(f"cannot bucket {n} rows")
    padded = base
    while padded < n:
        padded *= 2
    return padded


def pad_rows(rows: np.ndarray, *, base: int = 8) -> np.ndarray:
    """Pad a row batch to its :func:`bucket_size` by repeating row 0 —
    fixed jit shapes for variable batch sizes.  Callers slice the first
    ``len(rows)`` outputs back out; the padding rows are real (repeated)
    work, so results for them are well-defined and discarded."""
    rows = np.asarray(rows)
    padded = bucket_size(rows.shape[0], base=base)
    if padded == rows.shape[0]:
        return rows
    return np.concatenate(
        [rows, np.repeat(rows[:1], padded - rows.shape[0], axis=0)]
    )


def support_patterns(placements) -> tuple[np.ndarray, np.ndarray]:
    """Host-side bucketing of concrete placements by support pattern
    (which nodes hold any thread).  Returns the ``(n_buckets, s)`` 0/1
    support matrix — rows in lexicographic order, so the bucket layout is
    deterministic regardless of placement order — and the ``(P,)`` bucket
    id of every placement."""
    p = np.asarray(placements)
    sup = (p > 0).astype(np.int32)
    uniq, slab_id = np.unique(sup, axis=0, return_inverse=True)
    return uniq, slab_id.astype(np.int32).reshape(-1)


def simulate_grouped_batch(
    machine: MachineSpec,
    workload: Workload,
    placements: Array,  # (P, s) integer thread counts per node
    *,
    thread_classes: tuple[int, ...],
    support: Array | None = None,  # (n_buckets, s) support patterns
    slab_id: Array | None = None,  # (P,) bucket of each placement
    caps: Array | None = None,
    multipath: bool = False,
    elapsed: float = 1.0,
    early_exit: bool = True,
    bank_assignment: tuple[int, ...] | None = None,
) -> GroupedBatchResult:
    """Ground truth for a whole placement batch in one pass: bucket the
    placements by support pattern, build the base+interleave slab once per
    bucket, and run the structured progressive fill over the whole batch
    at once, each operand with the placements on its last axis.

    ``support`` / ``slab_id`` (from :func:`support_patterns`) may be
    passed in when the caller already bucketed on the host — mandatory
    when ``placements`` is traced; computed here otherwise.

    ``bank_assignment`` applies one page placement (Local-class backing
    node per placement node; ``None`` = node-local) to the whole batch —
    the scheduler evaluates "threads moved, pages stayed" placements
    through this hook.

    The slab build and the fill run under the named scopes ``slab`` and
    ``fill``, which the trace reduction reads from each device operation."""
    bank_assignment = canonical_bank_assignment(machine, bank_assignment)
    s = machine.n_nodes
    n = workload.n_threads
    topo = machine.topology
    placements = jnp.asarray(placements)
    if support is None or slab_id is None:
        support, slab_id = support_patterns(placements)
    support = jnp.asarray(support)
    slab_id = jnp.asarray(slab_id)

    comps = group_slab_components(
        machine, workload, thread_classes, bank_assignment
    )
    C = comps.base_read.shape[0]
    dtype = comps.base_read.dtype
    dense_caps, rr_caps, ww_caps = split_caps(machine, caps)
    node_rates = machine.node_rates().astype(dtype)
    n_links = topo.n_links
    inc = jnp.asarray(
        np.asarray(
            topo.route_incidence(multipath=multipath), np.float32
        ).reshape(s, s, n_links)
    )
    iterations = min(C * s, 2 * s + 2 * s * s + n_links) + 1

    def lanes(coeff):  # (C, s) -> (C, s, 1, 1)
        return coeff[:, :, None, None]

    # Every array from here on has the placements (or the buckets) on its
    # last axis.
    with jax.named_scope("slab"):
        used = support.T.astype(dtype)  # (s, n_buckets)
        il_row = used / jnp.maximum(used.sum(0), 1.0)
        b_ru = comps.base_read[..., None] + lanes(comps.il_read) * il_row
        b_wu = comps.base_write[..., None] + lanes(comps.il_write) * il_row
        offdiag = (1.0 - jnp.eye(s, dtype=dtype))[:, :, None]  # (s, s, 1)
        b_lu = jnp.einsum(
            "ckjb,kjl->cklb", (b_ru + b_wu) * offdiag, inc, precision=_HI
        )

    nf = placements.T.astype(dtype)  # (s, P)
    pt_row = nf / jnp.maximum(nf.sum(0), 1.0)
    # per-link charge of one unit of pt_row flow from node k (the diagonal
    # rows of inc are all-zero, so no off-diagonal mask needed)
    pt_link = jnp.einsum("jp,kjl->klp", pt_row, inc, precision=_HI)  # (s, L, P)
    # each placement's bucket: a gather of whole rows of the bucket-major
    # table, turned placement-last once, so the cost grows with P alone
    # even at one bucket per placement (search's leaf batches).  For a v5e
    # a gather along the last axis compiles to 4x the temp at 8 nodes and
    # 8192 placements.
    def bucket_rows(b):  # (..., n_buckets) -> (..., P)
        rows = b.reshape(-1, b.shape[-1]).T[slab_id]  # (P, R)
        return rows.T.reshape(b.shape[:-1] + slab_id.shape)

    ru = bucket_rows(b_ru) + lanes(comps.pt_read) * pt_row
    wu = bucket_rows(b_wu) + lanes(comps.pt_write) * pt_row
    lu = bucket_rows(b_lu) + lanes(comps.pt_read + comps.pt_write) * pt_link
    starts = tuple(int(v) for v in np.asarray(thread_classes, np.int64))
    mult = _group_multiplicities(starts, n, placements.T).astype(dtype)  # (C, s, P)
    with jax.named_scope("fill"):
        x, trips = _progressive_fill_structured(
            ru, wu, lu, mult, dense_caps, rr_caps, ww_caps, iterations,
            early_exit=early_exit,
        )
    weight = (mult * x)[:, :, None]  # (C, s, 1, P)
    read_flows = (weight * ru).sum(0) * elapsed  # (s, s, P)
    write_flows = (weight * wu).sum(0) * elapsed
    instructions = (weight[:, :, 0] * node_rates[:, None]).sum(0) * elapsed
    return GroupedBatchResult(
        read_flows=jnp.moveaxis(read_flows, -1, 0),
        write_flows=jnp.moveaxis(write_flows, -1, 0),
        instructions=instructions.T,
        throughput=weight.sum((0, 1, 2)),
        group_rates=jnp.moveaxis(x, -1, 0),
        fill_trips=trips,
    )


def simulate(
    machine: MachineSpec,
    workload: Workload,
    n_per_node: Array,
    *,
    elapsed: float = 1.0,
    noise_std: float = 0.0,
    background_bw: float = 0.0,
    key: Array | None = None,
    caps: Array | None = None,
    thread_classes: tuple[int, ...] | None = None,
    multipath: bool = False,
    bank_assignment: tuple[int, ...] | None = None,
) -> SimulationResult:
    """Run the workload on the machine under the given placement (threads
    per NUMA node) and emit ground truth + the paper-visible performance
    counters.

    ``bank_assignment`` places the Local class's pages: entry ``k`` names
    the node whose DIMMs back the local buffers of threads on node ``k``
    (``None`` = node-local, bit-for-bit today's behavior).  Redirected
    Local flows are charged like any other remote traffic: the remote
    path ``(k, bank)`` and every link on its route.

    ``caps`` substitutes the machine's capacity vector (slab order of
    :func:`machine_caps`) with traced values — the differentiable-forward
    hook ``repro.core.numa.calibrate`` fits machine parameters through;
    everything else about the machine (routes, rates, thread geometry)
    stays static structure.

    ``thread_classes`` is the static class-start partition from
    :func:`thread_class_starts` — required to stay on the group-collapsed
    hot path when the workload arrays are traced (inside jit/vmap their
    values cannot be inspected).  With concrete arrays it is inferred;
    otherwise the per-thread :func:`simulate_reference` path runs."""
    bank_assignment = canonical_bank_assignment(machine, bank_assignment)
    if thread_classes is None:
        thread_classes = _infer_thread_classes(workload)
    if thread_classes is None:
        return simulate_reference(
            machine, workload, n_per_node,
            elapsed=elapsed, noise_std=noise_std, background_bw=background_bw,
            key=key, caps=caps, multipath=multipath,
            bank_assignment=bank_assignment,
        )

    s = machine.n_nodes
    n = workload.n_threads
    n_per_node = jnp.asarray(n_per_node)
    starts = np.asarray(thread_classes, np.int64)
    if starts.size == 0 or starts[0] != 0 or (np.diff(starts) <= 0).any() or (
        starts[-1] >= n
    ):
        raise ValueError(f"invalid thread_classes {thread_classes} for {n} threads")
    C = starts.size
    rep = starts  # class representative = first member (static gather)

    node_rates = machine.node_rates()  # (s,)
    read_mix = _group_mix_rows(
        workload.read_static[rep],
        workload.read_local[rep],
        workload.read_per_thread[rep],
        workload.static_socket,
        n_per_node,
        bank_assignment,
    )
    write_mix = _group_mix_rows(
        workload.write_static[rep],
        workload.write_local[rep],
        workload.write_per_thread[rep],
        workload.static_socket,
        n_per_node,
        bank_assignment,
    )
    # (C, s, s): one class-c thread's unit demand on node k toward bank j
    read_unit = node_rates[None, :, None] * workload.read_bpi[rep][:, None, None] * read_mix
    write_unit = node_rates[None, :, None] * workload.write_bpi[rep][:, None, None] * write_mix

    usage, caps = _group_resource_tensor(
        machine, read_unit, write_unit, caps, multipath=multipath
    )
    mult = _group_multiplicities(thread_classes, n, n_per_node)  # (C, s)
    mult_f = mult.astype(usage.dtype)
    iterations = min(usage.shape[0], usage.shape[1]) + 1
    x = _progressive_fill_grouped(usage, mult_f.reshape(C * s), caps, iterations)
    xg = x.reshape(C, s)

    weight = mult_f * xg  # (C, s): threads x shared group rate
    read_flows = jnp.einsum(
        "ck,ckj->kj", weight, read_unit, precision=_HI
    ) * elapsed
    write_flows = jnp.einsum(
        "ck,ckj->kj", weight, write_unit, precision=_HI
    ) * elapsed
    instructions = (weight * node_rates[None, :]).sum(0) * elapsed

    node_of = _thread_nodes(n_per_node, n)
    class_of = np.searchsorted(starts, np.arange(n), side="right") - 1  # static
    rates = xg[class_of, node_of]

    return _finalize_result(
        rates, read_flows, write_flows, instructions, n_per_node,
        elapsed, noise_std, background_bw, key, s,
    )


def simulate_counters(
    machine: MachineSpec,
    workload: Workload,
    n_per_node: Array,
    **kwargs,
) -> CounterSample:
    """Just the performance counters of a simulated run — what a real
    profiling pass would hand the fitting pipeline."""
    return simulate(machine, workload, n_per_node, **kwargs).sample


def symmetric_placement(machine: MachineSpec, n_threads: int) -> Array:
    """Paper §5.1 run 1: equal threads per NUMA node, 1 thread/core."""
    assert n_threads % machine.n_nodes == 0, "symmetric run needs equal split"
    per = n_threads // machine.n_nodes
    assert per <= machine.cores_per_node
    return jnp.full((machine.n_nodes,), per, jnp.int32)


def asymmetric_placement(machine: MachineSpec, n_threads: int) -> Array:
    """Paper §5.1 run 2: same thread count, unequal split (Figure 7 uses a
    roughly 2:1 split on the first socket) — generalized to NUMA nodes.

    The 3:1 target split can be infeasible — e.g. 2 threads on a 2-node
    machine leave zero threads for the second node, and a full machine
    admits only the equal split.  Instead of asserting, fall back to the
    nearest valid split: node 0 gets the feasible count closest to the
    3:1 target (ties prefer the heavier node) that still yields an
    *unequal* split when any exists; a perfectly full machine returns the
    only (equal) valid placement.
    """
    s = machine.n_nodes
    cap = machine.cores_per_node
    if not 0 < n_threads <= s * cap:
        raise ValueError(f"{n_threads} threads do not fit {s} nodes x {cap} cores")
    target = -(-3 * n_threads // 4)

    def split_for(first: int) -> list[int] | None:
        rest = n_threads - first
        if rest < 0 or rest > (s - 1) * cap:
            return None
        others = [rest // (s - 1)] * (s - 1)
        others[0] += rest - sum(others)
        # spill overflow beyond per-socket capacity rightward; a no-op
        # whenever the heaped shape was already feasible (seed behaviour)
        for k in range(s - 2):
            if others[k] > cap:
                others[k + 1] += others[k] - cap
                others[k] = cap
        counts = [first] + others
        return counts if max(counts) <= cap else None

    candidates = sorted(
        range(min(cap, n_threads) + 1), key=lambda f: (abs(f - target), -f)
    )
    fallback = None
    for first in candidates:
        counts = split_for(first)
        if counts is None:
            continue
        if len(set(counts)) > 1:
            return jnp.asarray(counts, jnp.int32)
        if fallback is None:
            fallback = counts
    assert fallback is not None  # n_threads <= s * cap guarantees a split
    return jnp.asarray(fallback, jnp.int32)


def profile_pair(
    machine: MachineSpec,
    workload: Workload,
    *,
    noise_std: float = 0.0,
    background_bw: float = 0.0,
    key: Array | None = None,
    thread_classes: tuple[int, ...] | None = None,
) -> tuple[CounterSample, CounterSample]:
    """The paper's 2-run profiling protocol (§5.1): one symmetric and one
    asymmetric placement of the same thread count.  ``thread_classes``
    keeps traced callers (the batched fit) on the grouped solver."""
    if key is None:
        key = jax.random.PRNGKey(0)
    k_sym, k_asym = jax.random.split(key)
    sym = simulate_counters(
        machine,
        workload,
        symmetric_placement(machine, workload.n_threads),
        noise_std=noise_std,
        background_bw=background_bw,
        key=k_sym,
        thread_classes=thread_classes,
    )
    asym = simulate_counters(
        machine,
        workload,
        asymmetric_placement(machine, workload.n_threads),
        noise_std=noise_std,
        background_bw=background_bw,
        key=k_asym,
        thread_classes=thread_classes,
    )
    return sym, asym
