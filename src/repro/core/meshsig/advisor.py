"""Placement advisor — the Pandia use-case (paper §1) in both domains.

* TPU mesh: given a fitted :class:`MeshSignature`, rank candidate mesh
  aspect ratios by predicted step time WITHOUT compiling them — the three
  roofline terms are evaluated from the signature's predicted per-axis
  link bytes, predicted local HBM traffic, and compute scaling.
* NUMA machine: given a fitted :class:`BandwidthSignature` (2 profiling
  runs), rank candidate thread placements on any s >= 2 socket machine
  WITHOUT measuring them — the batched placement-sweep engine scores
  thousands of compositions in one vmapped call
  (:func:`rank_numa_placements`).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.meshsig.device_topology import DeviceTopology
from repro.core.meshsig.fit import MeshSignature


@dataclass(frozen=True)
class ChipSpec:
    """Per-chip roofline constants.  Callers pick a preset (or build their
    own) instead of monkeypatching module globals."""

    name: str
    peak_flops: float  # bf16 FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per ICI link (the scalar-model fallback)


CHIP_V5E = ChipSpec(name="v5e", peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9)
CHIP_V5P = ChipSpec(name="v5p", peak_flops=459e12, hbm_bw=2.765e12, ici_bw=100e9)

# Back-compat module aliases (historically monkeypatched; prefer ChipSpec)
PEAK_FLOPS = CHIP_V5E.peak_flops
HBM_BW = CHIP_V5E.hbm_bw
ICI_BW = CHIP_V5E.ici_bw


@dataclass
class MeshRanking:
    axis_sizes: dict[str, int]
    compute_s: float
    memory_s: float
    collective_s: float
    per_axis_s: dict[str, float]

    @property
    def step_s(self) -> float:
        # collectives overlap compute at best; the bound is the max term
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)


def rank_meshes(
    sig: MeshSignature,
    candidates: list[dict[str, int]],
    *,
    chip: ChipSpec = CHIP_V5E,
    topology: DeviceTopology | None = None,
    peak_flops: float | None = None,
    hbm_bw: float | None = None,
    ici_bw: float | None = None,
) -> list[MeshRanking]:
    """Evaluate every candidate mesh; returns rankings sorted by predicted
    step time (best first).

    With a :class:`DeviceTopology` the collective term routes every axis
    ring over the physical link graph (per-directed-link charging; a
    candidate's dict order picks the row-major device embedding), so two
    candidates with identical axis sizes can rank differently by how they
    lay onto the fabric.  Without one, each axis's bytes are divided by
    the chip's scalar ``ici_bw`` — the two agree exactly on a
    fully-connected uniform-bandwidth topology.  The explicit
    ``peak_flops`` / ``hbm_bw`` / ``ici_bw`` keywords override the chip's
    values (back-compat with the old module-global interface)."""
    peak_flops = chip.peak_flops if peak_flops is None else peak_flops
    hbm_bw = chip.hbm_bw if hbm_bw is None else hbm_bw
    ici_bw = chip.ici_bw if ici_bw is None else ici_bw
    out = []
    for axes in candidates:
        b = axes.get("data", 1) * axes.get("pod", 1)
        flops = sig.flops0 * sig.batch_shards0 / b  # per-device compute
        per_axis_bytes = sig.predict_axis_bytes(axes)
        if topology is None:
            per_axis_s = {a: v / ici_bw for a, v in per_axis_bytes.items()}
        else:
            per_axis_s = topology.per_axis_times(axes, per_axis_bytes)
        out.append(
            MeshRanking(
                axis_sizes=axes,
                compute_s=flops / peak_flops,
                memory_s=sig.predict_local_bytes(axes) / hbm_bw,
                collective_s=max(per_axis_s.values(), default=0.0),
                per_axis_s=per_axis_s,
            )
        )
    return sorted(out, key=lambda r: r.step_s)


# ---------------------------------------------------------------------------
# NUMA-domain advisor: rank thread placements from a fitted signature
# ---------------------------------------------------------------------------


@dataclass
class PlacementRanking:
    """One candidate placement's predicted cost (no measurement)."""

    placement: tuple[int, ...]  # threads per NUMA node
    remote_fraction: float  # predicted fraction of traffic leaving its node
    predicted_throughput: float  # roofline bound on the sum of thread rates,
    # each thread weighted by its node's relative core rate (a full-speed
    # thread on the fastest node counts 1.0)


@partial(jax.jit, static_argnames=("machine",))
def _placement_scores(  # bpi weights stay traced: one compile per machine
    machine, sig_read, sig_write, placements, read_bpi, write_bpi
) -> tuple[Array, Array]:
    """Signature-only roofline per placement: predict the (s, s) flow
    matrices the way §4 applies a signature (demand follows thread count),
    divide by every resource capacity, and bound the achievable rate by
    the worst utilization — the NUMA analogue of the mesh advisor's
    max-term step-time bound.

    Remote utilization is hop-aware: each ordered pair is scored against
    its per-pair (hop-attenuated) path capacity, and interconnect traffic
    is charged to every *link* on the pair's static route, so placements
    that push flow across a glued machine's node controllers rank below
    ones keeping traffic inside a quad.

    Demand is per-node-rate-aware: threads on a throttled or little node
    issue (and demand bandwidth) at that node's ``core_rate``, and the
    throughput bound weighs each thread by its node's relative rate — so
    the roofline trades compute asymmetry against locality instead of
    treating all nodes as equal."""
    from repro.core.bwsig import placement_matrix

    # Per-pair remote path caps (inf diagonal), the static pair->link
    # routing incidence and the per-node issue rates; all compile-time
    # constants per machine.
    rr_caps = machine.remote_read_caps()
    ww_caps = machine.remote_write_caps()
    route_inc = jnp.asarray(machine.topology.route_incidence())  # (s*s, L)
    link_caps = machine.link_caps()
    node_rates = machine.node_rates()
    rel_rates = node_rates / node_rates.max()

    def one(p):
        n = p.astype(jnp.float32)
        # demand-weighted node shares: a node's traffic scales with its
        # thread count *and* issue rate, so the remote fraction must too
        # (for homogeneous machines rel_rates == 1 and this is n / sum(n));
        # rel-rate mass can legitimately sum below 1, so guard with an
        # epsilon rather than the integer-thread-count clamp of 1.0
        nw = n * rel_rates
        w = nw / jnp.maximum(nw.sum(), 1e-9)
        demand_r = n * node_rates * read_bpi  # unsaturated bytes/s
        demand_w = n * node_rates * write_bpi
        flows_r = demand_r[:, None] * placement_matrix(sig_read, p)
        flows_w = demand_w[:, None] * placement_matrix(sig_write, p)

        utils = [
            # per-node bank capacities (scalar local_*_bw broadcasts; mixed
            # DIMM machines carry per-node tuples)
            flows_r.sum(0) / machine.node_local_bw("read"),
            flows_w.sum(0) / machine.node_local_bw("write"),
            (flows_r / rr_caps).reshape(-1),
            (flows_w / ww_caps).reshape(-1),
        ]
        if machine.n_links:
            # diagonal (self) pairs have empty routes => all-zero incidence
            # rows, so local flows drop out of the link charge on their own
            cross = (flows_r + flows_w).reshape(-1)
            charge = jnp.matmul(
                cross, route_inc, precision=jax.lax.Precision.HIGHEST
            )
            utils.append(charge / link_caps)
        worst = jnp.concatenate(utils).max()
        rate = jnp.minimum(1.0, 1.0 / jnp.maximum(worst, 1e-9))
        throughput = nw.sum() * rate

        remote_r = 1.0 - (w * jnp.diagonal(placement_matrix(sig_read, p))).sum()
        remote_w = 1.0 - (w * jnp.diagonal(placement_matrix(sig_write, p))).sum()
        weight = read_bpi + write_bpi
        frac = (read_bpi * remote_r + write_bpi * remote_w) / jnp.maximum(
            weight, 1e-9
        )
        return frac, throughput

    return jax.vmap(one)(placements)


def rank_numa_placements(
    machine,
    workload,
    *,
    noise_std: float = 0.0,
    key=None,
    max_placements: int | None = None,
    top_k: int | None = None,
    placements=None,
) -> list[PlacementRanking]:
    """Rank every one-thread-per-core placement of ``workload`` over
    ``machine``'s NUMA nodes (any node count, heterogeneous core rates
    included) by predicted throughput (desc), then predicted
    remote-traffic fraction (asc).

    Profiling cost is exactly the paper's 2 runs (cached); ranking cost is
    one vmapped matrix evaluation over the candidate set — no simulation
    or measurement per candidate.  ``placements`` overrides the candidate
    set (an ``(P, s)`` array): callers that already hold an enumerated or
    sampled set — the advisor service's per-machine placement cache, a
    search warm start — rank it directly instead of re-enumerating.
    """
    from repro.core.numa.evaluate import enumerate_placements, fitted_signatures

    (sig, _, _), = fitted_signatures(
        machine, workload, noise_std=noise_std,
        keys=None if key is None else jnp.stack([key]),
    )
    if placements is None:
        placements = enumerate_placements(
            machine, workload.n_threads, max_placements=max_placements
        )
    else:
        placements = jnp.asarray(placements)
    read_bpi = float(np.asarray(workload.read_bpi).mean())
    write_bpi = float(np.asarray(workload.write_bpi).mean())
    fracs, thrs = _placement_scores(
        machine, sig.read, sig.write, placements, read_bpi, write_bpi
    )
    fracs, thrs = np.asarray(fracs), np.asarray(thrs)
    order = np.lexsort((fracs, -thrs))
    if top_k is not None:
        order = order[:top_k]
    p_np = np.asarray(placements)
    return [
        PlacementRanking(
            placement=tuple(int(v) for v in p_np[i]),
            remote_fraction=float(fracs[i]),
            predicted_throughput=float(thrs[i]),
        )
        for i in order
    ]


def advise_schedule(
    machine,
    phased,
    *,
    model=None,
    candidates_per_phase: int = 8,
    beam_width: int = 24,
    allow_page_placement: bool = True,
):
    """Schedule a phased workload: the time-axis sibling of
    :func:`rank_numa_placements`.

    Where the one-shot ranker answers "which placement for this
    signature?", this answers "which placement *per phase*, and is
    reconfiguring at each boundary worth its cost?" — delegating to
    :func:`repro.core.numa.temporal.optimize_schedule` (candidate pool
    through the grouped solver, DP/beam over phase boundaries, optional
    page-placement states).  ``phased`` is a
    :class:`~repro.core.numa.temporal.PhasedWorkload`; ``model`` a
    :class:`~repro.core.numa.temporal.MigrationModel` (``None`` = default
    byte costs, machine-derived boundary bandwidth).  Returns the full
    :class:`~repro.core.numa.temporal.ScheduleSearchResult` — schedule,
    best-static baseline, and ``gain_pct`` never below zero.
    """
    from repro.core.numa.temporal import optimize_schedule

    return optimize_schedule(
        machine,
        phased,
        model=model,
        candidates_per_phase=candidates_per_phase,
        beam_width=beam_width,
        allow_page_placement=allow_page_placement,
    )


def numa_placement_bounds(machine, workload, placements, *, thread_classes=None):
    """Admissible per-placement upper bounds on total work rate
    (instructions/s), suitable for certifying search optimality.

    The ranking score above (:func:`_placement_scores`) is a *heuristic*
    roofline: it scales every thread by the single worst resource
    utilization, which can under-estimate a placement whose threads split
    across independently-saturating resources — i.e. it is NOT an
    admissible bound and must never be used to prune a branch-and-bound
    search.  This helper delegates to the simulator-side bound
    (:func:`repro.core.numa.search.placement_upper_bound`), which caps each
    thread group by its isolated-rate resource ceilings and therefore
    always sits at or above the simulated rate.
    """
    from repro.core.numa.search import placement_upper_bound

    return placement_upper_bound(
        machine, workload, placements, thread_classes=thread_classes
    )
