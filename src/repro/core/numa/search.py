"""Placement *search* — find the best thread placement without sweeping.

The composition space explodes past ~8 NUMA nodes (SNC-2 on an 8-socket
box is 16 nodes), so exhaustive :func:`repro.core.numa.evaluate.
sweep_placements` + ``evaluate_batch`` stops being an option exactly where
the paper's consumers (Pandia-style predictors, Smart Arrays) need answers
the fastest.  Two escapes, both driving the same grouped max-min solver
that powers the sweep:

* :func:`optimize_placement` — **relaxed gradient ascent**.  Fractional
  node thread-counts are parameterized as ``n_threads * softmax(logits)``
  and pushed through a continuous relaxation of the structured shared-slab
  fill (:func:`repro.core.numa.simulator._progressive_fill_structured`
  with the fixed-count loop, which is reverse-differentiable).  Multi-start
  AdamW (``repro.optim.adamw``) climbs predicted work rate, then the
  fractional optimum is rounded (largest remainder, cap-aware) and
  polished by exact single-thread moves.

* :func:`branch_and_bound` — **provably (1+gap)-optimal search** over
  compositions.  Thread->node assignment is contiguous, so a search node
  is a prefix ``(n_1 .. n_j)``; the upper bound combines the prefix's
  admissible per-group value with a suffix DP over the remaining nodes
  (see :func:`placement_upper_bound`).  Best-first expansion with an
  incumbent from cheap heuristic placements; leaves are exactly evaluated
  in jitted batches.

The admissible bound deserves a note: the mesh advisor's signature-only
worst-utilization roofline (``rank_numa_placements``) is a *ranking*
heuristic, not an upper bound — progressive filling lets unfrozen groups
keep climbing after the first bottleneck saturates, so the true work rate
can exceed ``n * min(1, 1/worst_util)``.  The bound used here is instead
built from per-group *isolated* rates: a (class c, node k) group's shared
rate in ANY placement is at most ``min(1, min_r cap_r / u_lower(c,k,r))``
where ``u_lower`` keeps only the placement-independent slab components
(static + local rows) plus the own-node per-thread (``>= 1/n``) and
interleave (``>= 1/s``) floors — every term only shrinks relative to the
real usage, and max-min filling never rates a group above its isolated
ceiling.  Summed with per-group totals clipped at ``cap_r / u_lower``
(a group of ``m`` threads moves at most ``cap/u`` regardless of ``m``),
this dominates the simulated work rate placement-for-placement.

Objective: total instruction rate (``instructions.sum()`` — thread rates
weighted by their node's issue rate), so heterogeneous (throttled /
big.LITTLE) machines optimize real work, not thread-rate count.
"""

from __future__ import annotations

import heapq
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.numa.machine import MachineSpec, canonical_bank_assignment
from repro.core.numa.simulator import (
    _group_multiplicities,
    _progressive_fill_structured,
    group_slab_components,
    pad_rows,
    simulate_grouped_batch,
    split_caps,
    thread_class_starts,
)
from repro.core.numa.workload import Workload
from repro.optim import adamw


class SearchResult(NamedTuple):
    """One found placement plus the effort receipts."""

    placement: tuple[int, ...]  # threads per NUMA node
    objective: float  # instructions/s of `placement` (exact simulation)
    evaluations: int  # exact batched-simulator placements evaluated
    nodes_expanded: int  # B&B tree nodes popped (0 for the optimizer)
    optimal: bool  # True iff B&B exhausted the tree within `gap`


def _classes_for(workload: Workload, thread_classes) -> tuple[int, ...]:
    return (
        thread_class_starts([workload])
        if thread_classes is None
        else tuple(int(v) for v in thread_classes)
    )


# ---------------------------------------------------------------------------
# Exact batched evaluation (shared by both modes and by tests)
# ---------------------------------------------------------------------------


@partial(
    jax.jit, static_argnames=("machine", "thread_classes", "bank_assignment")
)
def _objective_batch_jit(
    machine, wl_arrays, placements, thread_classes, bank_assignment=None
):
    # one bucket per placement: fixed shapes for any placement batch, so
    # the search loop reuses a single trace per padded batch size
    wl = Workload("search", *wl_arrays)
    sim = simulate_grouped_batch(
        machine,
        wl,
        placements,
        thread_classes=thread_classes,
        support=(placements > 0).astype(jnp.int32),
        slab_id=jnp.arange(placements.shape[0], dtype=jnp.int32),
        bank_assignment=bank_assignment,
    )
    return sim.instructions.sum(axis=1)


def exact_objectives(
    machine: MachineSpec,
    workload: Workload,
    placements,
    *,
    thread_classes: tuple[int, ...] | None = None,
    bank_assignment=None,
) -> np.ndarray:
    """Simulated work rate (instructions/s) of each placement — the ground
    truth both search modes optimize, batched through one jitted trace per
    padded batch size (rows padded by repetition, so no retrace churn).

    ``bank_assignment`` prices one page placement for the whole batch
    (``None`` = node-local): the scheduler's "threads moved, pages
    stayed" candidates are scored through this hook."""
    classes = _classes_for(workload, thread_classes)
    p = np.asarray(placements, np.int32)
    if p.ndim == 1:
        p = p[None, :]
    n_rows = p.shape[0]
    out = _objective_batch_jit(
        machine,
        tuple(workload[1:]),
        jnp.asarray(pad_rows(p)),
        classes,
        canonical_bank_assignment(machine, bank_assignment),
    )
    return np.asarray(out)[:n_rows]


# ---------------------------------------------------------------------------
# Relaxed continuous objective (differentiable)
# ---------------------------------------------------------------------------


def _continuous_multiplicities(
    class_starts: tuple[int, ...], n: int, p: Array
) -> Array:
    """:func:`repro.core.numa.simulator._group_multiplicities` for
    *fractional* node counts: the interval-overlap is piecewise linear in
    ``p``, so gradients flow."""
    bounds = jnp.asarray(class_starts + (n,), p.dtype)
    node_hi = jnp.cumsum(p)
    node_lo = node_hi - p
    lo = jnp.maximum(bounds[:-1, None], node_lo[None, :])
    hi = jnp.minimum(bounds[1:, None], node_hi[None, :])
    return jnp.maximum(hi - lo, 0.0)  # (C, s)


def relaxed_work_rate(
    machine: MachineSpec,
    workload: Workload,
    p: Array,
    *,
    thread_classes: tuple[int, ...] | None = None,
    tau: float = 0.25,
) -> Array:
    """Differentiable work rate of a *fractional* placement ``p`` (positive
    reals summing to ``n_threads``).  The hard support indicator becomes
    ``p / (p + tau)`` so emptying a node is a smooth event; at integer
    placements with ``tau -> 0`` this approaches the exact grouped solve."""
    classes = _classes_for(workload, thread_classes)
    s = machine.n_nodes
    n = workload.n_threads
    topo = machine.topology
    comps = group_slab_components(machine, workload, classes)
    C = comps.base_read.shape[0]
    dtype = comps.base_read.dtype
    dense_caps, rr_caps, ww_caps = split_caps(machine)
    offdiag = (1.0 - jnp.eye(s, dtype=dtype))[None, :, :]
    n_links = topo.n_links
    iterations = min(C * s, 2 * s + 2 * s * s + n_links) + 1

    p = p.astype(dtype)
    pt_row = p / jnp.maximum(p.sum(), 1.0)
    used = p / (p + tau)
    il_row = used / jnp.maximum(used.sum(), 1.0)
    ru = (
        comps.base_read
        + comps.pt_read[:, :, None] * pt_row[None, None, :]
        + comps.il_read[:, :, None] * il_row[None, None, :]
    )
    wu = (
        comps.base_write
        + comps.pt_write[:, :, None] * pt_row[None, None, :]
        + comps.il_write[:, :, None] * il_row[None, None, :]
    )
    inc = jnp.asarray(
        np.asarray(topo.route_incidence(), np.float32).reshape(s, s, n_links)
    )
    lu = jnp.einsum(
        "ckj,kjl->ckl", (ru + wu) * offdiag, inc,
        precision=jax.lax.Precision.HIGHEST,  # as the exact fill's slab
    )
    mult = _continuous_multiplicities(classes, n, p)  # (C, s)
    # one placement: the fill's trailing placement axis has length 1
    x, _ = _progressive_fill_structured(
        ru[..., None],
        wu[..., None],
        lu[..., None],
        mult[..., None],
        dense_caps,
        rr_caps,
        ww_caps,
        iterations,
        early_exit=False,  # keep the fixed loop: reverse-differentiable
    )
    node_rates = machine.node_rates().astype(dtype)
    return (mult * x[..., 0] * node_rates[None, :]).sum()


# ---------------------------------------------------------------------------
# Mode (a): multi-start gradient ascent + round-and-polish
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=("machine", "thread_classes", "steps", "lr", "tau"),
)
def _ascend_starts_jit(
    machine, wl_arrays, logits0, thread_classes, steps, lr, tau
):
    wl = Workload("search", *wl_arrays)
    n = wl.n_threads
    cap = float(machine.cores_per_node)
    scale = n * jnp.max(machine.node_rates())

    def loss(logits):
        p = n * jax.nn.softmax(logits)
        obj = relaxed_work_rate(
            machine, wl, p, thread_classes=thread_classes, tau=tau
        )
        over = jnp.maximum(p - cap, 0.0)
        return -(obj / scale) + 10.0 * jnp.sum(over * over)

    grad = jax.vmap(jax.grad(loss))
    params = {"logits": logits0}
    state = adamw.init(params)

    def step(carry, _):
        params, state = carry
        # the relaxed fill is only piecewise-smooth: at freeze boundaries a
        # start can emit non-finite cotangents — zero them instead of
        # poisoning the whole trajectory
        g = {"logits": jnp.nan_to_num(grad(params["logits"]), nan=0.0, posinf=0.0, neginf=0.0)}
        params, state = adamw.update(
            g, state, params, lr=lr, weight_decay=0.0
        )
        return (params, state), None

    (params, _), _ = jax.lax.scan(step, (params, state), None, length=steps)
    return n * jax.nn.softmax(params["logits"], axis=-1)


def _round_capped(p_cont: np.ndarray, n: int, cap: int) -> np.ndarray:
    """Largest-remainder rounding of a fractional placement onto the
    integer composition simplex with per-node caps."""
    q = np.clip(p_cont, 0.0, cap)
    base = np.floor(q).astype(np.int64)
    frac = q - base
    rem = n - int(base.sum())
    order = list(np.argsort(-frac))
    while rem > 0:
        for k in order:
            if rem == 0:
                break
            if base[k] < cap:
                base[k] += 1
                rem -= 1
    while rem < 0:
        for k in reversed(order):
            if rem == 0:
                break
            if base[k] > 0:
                base[k] -= 1
                rem += 1
    return base.astype(np.int32)


def _neighbours(p: np.ndarray, cap: int) -> list[np.ndarray]:
    """All single-thread moves (src with a thread, dst with headroom)."""
    s = p.shape[0]
    out = []
    for src in range(s):
        if p[src] == 0:
            continue
        for dst in range(s):
            if dst == src or p[dst] >= cap:
                continue
            q = p.copy()
            q[src] -= 1
            q[dst] += 1
            out.append(q)
    return out


def optimize_placement(
    machine: MachineSpec,
    workload: Workload,
    *,
    thread_classes: tuple[int, ...] | None = None,
    n_starts: int = 16,
    steps: int = 150,
    lr: float = 0.25,
    tau: float = 0.25,
    seed: int = 0,
    polish: bool = True,
    max_polish_passes: int | None = None,
) -> SearchResult:
    """Multi-start relaxed gradient ascent on predicted work rate, then
    round-and-polish: the fractional optima are snapped to integer
    compositions (largest remainder, cap-aware) and hill-climbed with
    exact single-thread moves.  Cost is independent of the composition
    count — this is the mode for 16+-node machines where enumeration is
    infeasible."""
    classes = _classes_for(workload, thread_classes)
    s = machine.n_nodes
    n = workload.n_threads
    cap = machine.cores_per_node
    if not 0 < n <= s * cap:
        raise ValueError(f"{n} threads do not fit {s} nodes x {cap} cores")

    rng = np.random.default_rng(seed)
    logits0 = np.zeros((n_starts, s), np.float32)
    # start 0: uniform spread; a few one-hot-ish packers; the rest random
    for i in range(1, min(n_starts, s + 1)):
        logits0[i, (i - 1) % s] = 3.0
    if n_starts > s + 1:
        logits0[s + 1 :] = rng.normal(0.0, 1.5, (n_starts - s - 1, s))
    p_frac = np.asarray(
        _ascend_starts_jit(
            machine,
            tuple(workload[1:]),
            jnp.asarray(logits0),
            classes,
            int(steps),
            float(lr),
            float(tau),
        )
    )

    seen: dict[tuple[int, ...], None] = {}
    uniform = np.full(s, n / s)
    for row in p_frac:
        if not np.all(np.isfinite(row)):  # a diverged start; fall back
            row = uniform
        seen.setdefault(tuple(int(v) for v in _round_capped(row, n, cap)), None)
    candidates = [np.asarray(c, np.int32) for c in seen]
    values = exact_objectives(
        machine, workload, np.stack(candidates), thread_classes=classes
    )
    evals = len(candidates)
    best_i = int(np.argmax(values))
    best, best_val = candidates[best_i], float(values[best_i])

    if polish:
        passes = 4 * s if max_polish_passes is None else max_polish_passes
        for _ in range(passes):
            moves = _neighbours(best, cap)
            if not moves:
                break
            vals = exact_objectives(
                machine, workload, np.stack(moves), thread_classes=classes
            )
            evals += len(moves)
            i = int(np.argmax(vals))
            if float(vals[i]) <= best_val * (1.0 + 1e-7):
                break
            best, best_val = moves[i], float(vals[i])

    return SearchResult(
        placement=tuple(int(v) for v in best),
        objective=best_val,
        evaluations=evals,
        nodes_expanded=0,
        optimal=False,
    )


# ---------------------------------------------------------------------------
# Mode (b): branch and bound with an admissible per-group roofline
# ---------------------------------------------------------------------------


def _group_rate_ceilings(
    machine: MachineSpec, workload: Workload, classes: tuple[int, ...]
) -> np.ndarray:
    """``(C, s)`` admissible per-thread rate ceiling ``cap_r / u_lower`` of
    a (class, node) group, *before* the demand clip at 1.0 (callers clip
    per-group totals instead: ``m`` threads move at most
    ``min(m, ceiling)``).  ``u_lower`` keeps only usage components every
    placement is guaranteed to charge — see the module docstring."""
    s = machine.n_nodes
    n = workload.n_threads
    comps = jax.tree.map(np.asarray, group_slab_components(machine, workload, classes))
    own = np.eye(s)[None, :, :]  # (1, s, s): the own-node bank column
    # own-node floors: pt_row[k] >= 1/n and il_row[k] >= 1/s whenever the
    # group exists (it holds at least one of the n threads; at most s
    # nodes are used) — every other pt/il contribution is bounded below
    # by zero and dropped
    ru = comps.base_read + (
        comps.pt_read[:, :, None] / n + comps.il_read[:, :, None] / s
    ) * own
    wu = comps.base_write + (
        comps.pt_write[:, :, None] / n + comps.il_write[:, :, None] / s
    ) * own

    dense_caps, rr_caps, ww_caps = (
        np.asarray(a, np.float64) for a in split_caps(machine)
    )
    bank_r = dense_caps[:s]
    bank_w = dense_caps[s : 2 * s]
    link_caps = dense_caps[2 * s :]
    offdiag = 1.0 - np.eye(s)

    with np.errstate(divide="ignore"):
        # bank capacities: usage row j vs cap j
        r_banks = np.where(ru > 0, bank_r[None, None, :] / np.maximum(ru, 1e-30), np.inf)
        w_banks = np.where(wu > 0, bank_w[None, None, :] / np.maximum(wu, 1e-30), np.inf)
        ceil = np.minimum(r_banks.min(axis=2), w_banks.min(axis=2))  # (C, s)
        # remote per-pair path capacities (diagonal caps are inf already)
        rr = np.where(
            ru * offdiag > 0,
            np.asarray(rr_caps)[None, :, :] / np.maximum(ru * offdiag, 1e-30),
            np.inf,
        )
        wwp = np.where(
            wu * offdiag > 0,
            np.asarray(ww_caps)[None, :, :] / np.maximum(wu * offdiag, 1e-30),
            np.inf,
        )
        ceil = np.minimum(ceil, np.minimum(rr.min(axis=2), wwp.min(axis=2)))
        if machine.n_links:
            inc = np.asarray(
                machine.topology.route_incidence(), np.float64
            ).reshape(s, s, machine.n_links)
            lu = np.einsum("ckj,kjl->ckl", (ru + wu) * offdiag, inc)
            links = np.where(
                lu > 0, link_caps[None, None, :] / np.maximum(lu, 1e-30), np.inf
            )
            ceil = np.minimum(ceil, links.min(axis=2))
    return ceil  # (C, s) in threads-at-full-rate units


class _BoundTables(NamedTuple):
    value: np.ndarray  # (s, n+1, cap+1) admissible value of t threads at
    #                    offset m on node j (thread->node order is contiguous)
    suffix: np.ndarray  # (s+1, n+1) best completion value from (node, offset)


def _bound_tables(
    machine: MachineSpec, workload: Workload, classes: tuple[int, ...]
) -> _BoundTables:
    s = machine.n_nodes
    n = workload.n_threads
    cap = machine.cores_per_node
    ceil = _group_rate_ceilings(machine, workload, classes)  # (C, s)
    rates = np.asarray(machine.node_rates(), np.float64)
    starts = np.asarray(classes + (n,), np.int64)
    C = len(classes)
    # cum[c, m] = threads of class c among the first m threads
    cum = np.zeros((C, n + 1), np.int64)
    for c in range(C):
        lo, hi = starts[c], starts[c + 1]
        cum[c] = np.clip(np.arange(n + 1), lo, hi) - lo

    value = np.zeros((s, n + 1, cap + 1))
    t_grid = np.arange(cap + 1)
    for j in range(s):
        acc = np.zeros((n + 1, cap + 1))
        for c in range(C):
            hi = cum[c][np.minimum(np.arange(n + 1)[:, None] + t_grid[None, :], n)]
            acc += np.minimum(hi - cum[c][:, None], ceil[c, j])
        value[j] = acc * rates[j]

    suffix = np.full((s + 1, n + 1), -np.inf)
    suffix[s, n] = 0.0
    for j in range(s - 1, -1, -1):
        for m in range(n + 1):
            t_max = min(cap, n - m)
            cand = value[j, m, : t_max + 1] + suffix[j + 1, m : m + t_max + 1]
            suffix[j, m] = cand.max() if cand.size else -np.inf
    return _BoundTables(value=value, suffix=suffix)


def placement_upper_bound(
    machine: MachineSpec,
    workload: Workload,
    placements,
    *,
    thread_classes: tuple[int, ...] | None = None,
) -> np.ndarray:
    """Admissible work-rate roofline of each placement: for every
    placement ``p``, ``bound(p) >= exact_objectives(p)`` (the branch-and-
    bound invariant; pinned by tests on random placements).  Vectorized
    host-side lookup into the same per-node value tables B&B prunes with."""
    classes = _classes_for(workload, thread_classes)
    tables = _bound_tables(machine, workload, classes)
    p = np.asarray(placements, np.int64)
    if p.ndim == 1:
        p = p[None, :]
    offs = np.concatenate(
        [np.zeros((p.shape[0], 1), np.int64), np.cumsum(p, axis=1)[:, :-1]], axis=1
    )
    s = machine.n_nodes
    out = np.zeros(p.shape[0])
    for j in range(s):
        out += tables.value[j, offs[:, j], p[:, j]]
    return out


def _heuristic_seeds(machine: MachineSpec, n: int) -> list[np.ndarray]:
    """Cheap incumbents: spread the threads as evenly as caps allow over
    the k fastest nodes, for every k that fits."""
    s = machine.n_nodes
    cap = machine.cores_per_node
    order = np.argsort(-np.asarray(machine.node_rates(), np.float64), kind="stable")
    seeds = []
    for k in range(1, s + 1):
        if k * cap < n:
            continue
        p = np.zeros(s, np.int64)
        chosen = order[:k]
        base, extra = divmod(n, k)
        if base >= cap and extra:
            continue
        for i, node in enumerate(chosen):
            p[node] = min(cap, base + (1 if i < extra else 0))
        if p.sum() == n:
            seeds.append(p.astype(np.int32))
    return seeds


def advisor_warm_seeds(
    machine: MachineSpec,
    workload: Workload,
    *,
    top_k: int = 8,
    max_placements: int = 4096,
    noise_std: float = 0.0,
    key=None,
) -> list[np.ndarray]:
    """Incumbent seeds from the advisor's *signature-only* ranking
    (:func:`repro.core.meshsig.advisor.rank_numa_placements`): the top-k
    placements by the cheap roofline score, to be evaluated *exactly* by
    the caller.  The ranking costs one cached 2-run fit plus a vmapped
    matrix pass over (a sample of) the composition space — no simulation
    per candidate — so it is a legitimate warm start even on machines
    whose spaces cannot be enumerated (``max_placements`` caps the ranked
    sample there).  The roofline is a heuristic, NOT admissible
    (:func:`repro.core.meshsig.advisor.numa_placement_bounds`): seeds only
    ever *raise* the incumbent, they never prune — so a warm start can
    never worsen the certificate.

    Returns no seeds when the thread count does not divide evenly over the
    nodes: the 2-run fit needs the symmetric profiling placement, so the
    ranking is unavailable and the caller falls back to its heuristic
    seeds alone."""
    from repro.core.meshsig.advisor import rank_numa_placements

    if workload.n_threads % machine.n_nodes != 0:
        return []
    ranked = rank_numa_placements(
        machine,
        workload,
        top_k=top_k,
        max_placements=max_placements,
        noise_std=noise_std,
        key=key,
    )
    return [np.asarray(r.placement, np.int32) for r in ranked]


def branch_and_bound(
    machine: MachineSpec,
    workload: Workload,
    *,
    thread_classes: tuple[int, ...] | None = None,
    gap: float = 0.0,
    max_nodes: int = 200_000,
    leaf_batch: int = 64,
    seed_placements: Sequence | None = None,
    advisor_seeds: int = 0,
    advisor_max_placements: int = 4096,
) -> SearchResult:
    """Best-first branch and bound over thread compositions.  Returns a
    placement whose exact work rate is within ``gap`` (relative) of the
    global optimum when the tree is exhausted (``optimal=True``); hitting
    ``max_nodes`` degrades gracefully to the incumbent.

    The tree assigns node counts left to right; a node's bound is its
    prefix value plus the suffix DP completion (both admissible — see
    :func:`placement_upper_bound`).  Leaves are evaluated exactly in
    jitted batches of ``leaf_batch``; pure-python everywhere else, so the
    search itself never compiles anything new.

    ``advisor_seeds > 0`` warm-starts the incumbent from the advisor's
    signature-only ranking (:func:`advisor_warm_seeds` top-k, evaluated
    exactly alongside the heuristic seeds).  A better initial incumbent
    tightens the prune level from the first pop, so the warm start can
    only shrink the expanded tree — it never loosens the certificate
    (seeds never prune; only exact evaluations move the incumbent)."""
    classes = _classes_for(workload, thread_classes)
    s = machine.n_nodes
    n = workload.n_threads
    cap = machine.cores_per_node
    if not 0 < n <= s * cap:
        raise ValueError(f"{n} threads do not fit {s} nodes x {cap} cores")
    tables = _bound_tables(machine, workload, classes)
    value, suffix = tables.value, tables.suffix

    seeds = [np.asarray(p, np.int32) for p in (seed_placements or [])]
    if advisor_seeds > 0:
        seeds.extend(
            advisor_warm_seeds(
                machine,
                workload,
                top_k=advisor_seeds,
                max_placements=advisor_max_placements,
            )
        )
    seeds.extend(_heuristic_seeds(machine, n))
    incumbent_p = seeds[0]
    vals = exact_objectives(machine, workload, np.stack(seeds), thread_classes=classes)
    evals = len(seeds)
    best_i = int(np.argmax(vals))
    incumbent_p, incumbent = seeds[best_i], float(vals[best_i])

    def prune_level() -> float:
        return incumbent * (1.0 + gap)

    # heap entries: (-bound, tiebreak, depth, offset, prefix_value, prefix)
    root_bound = suffix[0, 0]
    heap = [(-root_bound, 0, 0, 0, 0.0, ())]
    tiebreak = 1
    expanded = 0
    leaves: list[tuple[float, tuple[int, ...]]] = []
    exhausted = True

    def flush_leaves():
        nonlocal incumbent, incumbent_p, evals
        if not leaves:
            return
        batch = np.asarray([p for _, p in leaves], np.int32)
        vals = exact_objectives(machine, workload, batch, thread_classes=classes)
        evals += len(leaves)
        i = int(np.argmax(vals))
        if float(vals[i]) > incumbent:
            incumbent = float(vals[i])
            incumbent_p = batch[i]
        leaves.clear()

    while heap:
        neg_bound, _, depth, off, pval, prefix = heapq.heappop(heap)
        if -neg_bound <= prune_level():
            break  # best-first: nothing left can beat the incumbent
        if expanded >= max_nodes:
            exhausted = False
            break
        expanded += 1
        if depth == s - 1:
            # the last node count is forced; emit a leaf
            t = n - off
            if 0 <= t <= cap:
                leaves.append((pval + value[depth, off, t], prefix + (t,)))
                if len(leaves) >= leaf_batch:
                    flush_leaves()
            continue
        remaining_cap = (s - depth - 1) * cap
        t_lo = max(0, n - off - remaining_cap)
        t_hi = min(cap, n - off)
        for t in range(t_lo, t_hi + 1):
            child_val = pval + value[depth, off, t]
            child_bound = child_val + suffix[depth + 1, off + t]
            if child_bound <= prune_level():
                continue
            heapq.heappush(
                heap,
                (-child_bound, tiebreak, depth + 1, off + t, child_val, prefix + (t,)),
            )
            tiebreak += 1
    flush_leaves()

    return SearchResult(
        placement=tuple(int(v) for v in incumbent_p),
        objective=incumbent,
        evaluations=evals,
        nodes_expanded=expanded,
        optimal=exhausted,
    )
