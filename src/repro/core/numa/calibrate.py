"""Learned topology calibration — the inverse problem of the simulator.

The paper parameterizes its bandwidth model from counters sampled in two
carefully chosen runs; every ``MachineSpec`` in this repo was, until now,
hand-specified.  This module solves the *inverse* problem the ROADMAP's
"Learned topology fit" item asks for: given a set of ``(placement,
observed counters)`` samples — produced by the simulator for synthetic
ground truth, or by any ``bwsig/counters.py``-shaped counter trace from a
real machine — recover the free parameters of a machine:

* the per-link interconnect bandwidths (through the topology's
  symmetry/structure packing, :func:`repro.core.graphtop.link_groups` —
  the same packing + AdamW-in-log-space recipe
  :mod:`repro.core.meshsig.calibrate` runs for ICI links),
* ``hop_attenuation``, and
* the (per-node) ``local_read_bw`` / ``local_write_bw`` tuples,

holding the structural template fixed: node count, core rates, routing
tables and the remote path base capacities (the ratio-characterized
quantities of paper Figure 2, measurable from a single remote STREAM-style
run) all come from the template spec.

The fit is two-stage, mirroring the paper's philosophy of cheap seeding
plus model refinement:

1. **Counter seeding** (:func:`seed_parameters`) — closed-form lower
   bounds read straight off the samples.  Each bank's capacity is seeded
   by the largest total it was ever observed to move; per-pair flows are
   recovered from the bank-perspective remote counters by the same
   thread-count apportionment rule ``bwsig.fit`` uses (exact whenever one
   remote source is active, which the probe suite guarantees), charged
   along the static routes to seed every link; multi-hop pair flows
   lower-bound the attenuation.  On a saturating probe sweep these bounds
   are *tight* — the seed alone is often within a few percent.
2. **Projected gradient over the differentiable simulator**
   (:func:`fit_machine`) — all parameters are refined jointly by AdamW in
   log space (positivity by reparameterization, the smooth form of a
   projection) against the squared relative counter error of the full
   max-min-fair forward model, one jitted ``lax.scan`` of
   ``value_and_grad`` steps with the machine template static and only the
   capacity vector traced (``simulate(..., caps=...)``).

The probe suite (:func:`probe_suite`) is the sweep design that makes the
problem identifiable: per-node local probes saturate each bank in each
direction, per-ordered-pair static probes saturate thin links and the
hop-attenuated remote paths (these include the paper's 2-run
symmetric/asymmetric pair), and spread interleave/static-sink probes
saturate fat shared links that no single pair can fill (an SNC socket's
QPI port carries both directions of every cross-socket pair at once).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import Array

from repro.core.bwsig.counters import CounterSample
from repro.core.bwsig.fit import _remote_source_weights
from repro.core.numa.machine import GB, MachineSpec
from repro.core.numa.simulator import (
    asymmetric_placement,
    class_starts_from_arrays,
    simulate,
    thread_class_starts,
)
from repro.core.numa.topology import LinkGroups, from_fit, link_groups
from repro.core.numa.workload import Workload, mixed_workload
from repro.optim import adamw

_EPS = 1e-9
# Finite stand-in for the unconstrained diagonal of the remote-path caps:
# its usage column is structurally zero, so any value never binds — but a
# finite one keeps the progressive-fill linearization coefficients finite
# under reverse-mode AD (inf residuals turn 0-cotangent products into NaN).
_UNUSED_CAP = 1e5


class CalibrationSamples(NamedTuple):
    """A counter sweep: ``P`` profiling runs of known workloads/placements.

    ``wl_arrays`` stacks every array field of the run's :class:`Workload`
    over the leading sample axis (the jit boundary cannot carry the name
    string); counters are bytes (or instructions) observed over
    ``elapsed`` seconds, bank-perspective, exactly the
    :class:`~repro.core.bwsig.counters.CounterSample` view real hardware
    exposes."""

    wl_arrays: tuple[Array, ...]  # leaves (P, n) / (P,)
    placements: Array  # (P, s) int32
    local_read: Array  # (P, s)
    remote_read: Array  # (P, s)
    local_write: Array  # (P, s)
    remote_write: Array  # (P, s)
    instructions: Array  # (P, s)
    elapsed: Array  # (P,)

    @property
    def n_samples(self) -> int:
        """Number of profiled placements in the sample set."""
        return int(self.placements.shape[0])

    @property
    def n_nodes(self) -> int:
        """NUMA node count of the machine the samples came from."""
        return int(self.placements.shape[1])


class CalibrationParams(NamedTuple):
    """Free parameters, unconstrained: capacities live in log space and
    the attenuation behind a sigmoid, so plain gradient steps stay inside
    the feasible set (the smooth projection)."""

    log_link_bw: Array  # (n_groups,)
    log_local_read: Array  # (s,)
    log_local_write: Array  # (s,)
    att_raw: Array  # () — hop_attenuation = sigmoid(att_raw)


class SampleDiagnostics(NamedTuple):
    """Ingestion receipts from :func:`clean_samples`: how many rows
    arrived, how many survived, and why the rest were rejected — the
    counted evidence a production counter feed is (or is not) healthy."""

    n_total: int
    n_kept: int
    n_rejected: int
    reasons: tuple[str, ...]  # one short description per reject category

    @property
    def reject_rate(self) -> float:
        """Fraction of ingested rows rejected (0.0 on an empty batch)."""
        return self.n_rejected / self.n_total if self.n_total else 0.0


class CalibrationResult(NamedTuple):
    """A fitted machine plus the optimizer's receipts (loss trajectory,
    seed-vs-final loss, and the raw parameters behind the spec).
    ``diagnostics`` carries the sample-ingestion receipts when the fit
    cleaned its input (``fit_machine(clean=True)``, the default)."""

    machine: MachineSpec  # the fitted spec (concrete, validated)
    params: CalibrationParams
    groups: LinkGroups
    loss_history: np.ndarray  # (steps,)
    seed_loss: float
    final_loss: float
    diagnostics: "SampleDiagnostics | None" = None


# ---------------------------------------------------------------------------
# Sample construction
# ---------------------------------------------------------------------------


def _workload_arrays(wl: Workload) -> tuple[Array, ...]:
    return tuple(wl[1:])


def _stack_probe_workloads(wls: Sequence[Workload]) -> tuple[Array, ...]:
    n_threads = {w.n_threads for w in wls}
    if len(n_threads) != 1:
        raise ValueError(f"probe workloads must share a thread count, got {n_threads}")
    return tuple(
        jnp.stack(parts) for parts in zip(*(_workload_arrays(w) for w in wls))
    )


def samples_from_counters(
    workloads: Sequence[Workload],
    placements,
    counters: Sequence[CounterSample],
) -> CalibrationSamples:
    """Package an externally measured counter trace (one
    :class:`CounterSample` per known workload+placement run) for fitting —
    the path a real machine's PCM trace takes into the calibrator."""
    if not len(workloads) == len(counters):
        raise ValueError("one CounterSample per workload run required")
    placements = jnp.asarray(placements, jnp.int32)
    if placements.shape[0] != len(workloads):
        raise ValueError("one placement per workload run required")
    # each CounterSample records the placement of its own run — a silent
    # order mismatch against the placements argument would apportion the
    # remote counters by the wrong thread counts and corrupt the fit
    for k, c in enumerate(counters):
        recorded = np.asarray(c.n_per_socket)
        if not np.array_equal(recorded, np.asarray(placements[k])):
            raise ValueError(
                f"run {k}: placement {np.asarray(placements[k]).tolist()} "
                f"disagrees with the counter sample's recorded placement "
                f"{recorded.tolist()}"
            )
    return CalibrationSamples(
        wl_arrays=_stack_probe_workloads(workloads),
        placements=placements,
        local_read=jnp.stack([c.local_read for c in counters]),
        remote_read=jnp.stack([c.remote_read for c in counters]),
        local_write=jnp.stack([c.local_write for c in counters]),
        remote_write=jnp.stack([c.remote_write for c in counters]),
        instructions=jnp.stack([c.instructions for c in counters]),
        elapsed=jnp.stack([jnp.asarray(c.elapsed, jnp.float32) for c in counters]),
    )


def _take_rows(samples: CalibrationSamples, keep: np.ndarray) -> CalibrationSamples:
    """Index every leaf of a sample set by the ``keep`` row indices."""
    take = lambda arr: jnp.asarray(np.asarray(arr)[keep])
    return CalibrationSamples(
        wl_arrays=tuple(take(a) for a in samples.wl_arrays),
        placements=take(samples.placements),
        local_read=take(samples.local_read),
        remote_read=take(samples.remote_read),
        local_write=take(samples.local_write),
        remote_write=take(samples.remote_write),
        instructions=take(samples.instructions),
        elapsed=take(samples.elapsed),
    )


def clean_samples(
    samples: CalibrationSamples,
    *,
    on_empty: str = "raise",
) -> tuple[CalibrationSamples, SampleDiagnostics]:
    """NaN-guard a sample batch before it can poison the AdamW fit.

    A row (one profiled placement) is rejected when any of its workload
    arrays, placement entries or counters is non-finite, any counter is
    negative, or its elapsed time is not strictly positive — the three
    corruption modes a production counter feed actually exhibits (dropped
    MSR reads surface as NaN/garbage, wrap-around as negatives, a dead
    sampling interval as elapsed 0).  Returns the surviving rows plus a
    :class:`SampleDiagnostics` counting what was dropped and why.

    ``on_empty="raise"`` (default) raises a descriptive ``ValueError``
    when *no* row survives — a silently empty fit input is the worst
    possible outcome; ``on_empty="ignore"`` returns the empty batch for
    callers that accumulate across batches and check later.
    """
    P = samples.n_samples
    leaves = (
        samples.wl_arrays
        + (
            samples.placements,
            samples.local_read,
            samples.remote_read,
            samples.local_write,
            samples.remote_write,
            samples.instructions,
            samples.elapsed,
        )
    )
    finite = np.ones((P,), bool)
    for arr in leaves:
        a = np.asarray(arr, np.float64).reshape(P, -1)
        finite &= np.isfinite(a).all(axis=1)
    counters = np.concatenate(
        [
            np.asarray(c, np.float64).reshape(P, -1)
            for c in (
                samples.local_read, samples.remote_read,
                samples.local_write, samples.remote_write,
                samples.instructions,
            )
        ],
        axis=1,
    )
    with np.errstate(invalid="ignore"):
        nonneg = ~(counters < 0).any(axis=1)
        pos_elapsed = np.asarray(samples.elapsed, np.float64) > 0
    keep_mask = finite & nonneg & pos_elapsed
    reasons = []
    for mask, what in (
        (~finite, "non-finite values"),
        (finite & ~nonneg, "negative counters"),
        (finite & nonneg & ~pos_elapsed, "non-positive elapsed time"),
    ):
        idx = np.flatnonzero(mask)
        if idx.size:
            shown = ", ".join(str(i) for i in idx[:8])
            more = f", +{idx.size - 8} more" if idx.size > 8 else ""
            reasons.append(f"{idx.size} row(s) with {what} (rows {shown}{more})")
    diag = SampleDiagnostics(
        n_total=P,
        n_kept=int(keep_mask.sum()),
        n_rejected=int(P - keep_mask.sum()),
        reasons=tuple(reasons),
    )
    if diag.n_kept == 0 and on_empty == "raise":
        raise ValueError(
            f"all {P} calibration samples rejected: " + "; ".join(reasons)
            if reasons
            else "calibration sample batch is empty"
        )
    if diag.n_rejected == 0:
        return samples, diag
    return _take_rows(samples, np.flatnonzero(keep_mask)), diag


def concat_samples(batches: Sequence[CalibrationSamples]) -> CalibrationSamples:
    """Concatenate sample batches along the sample axis — the
    accumulation step of a production recalibration stream, where
    counters arrive machine-by-machine in partial sweeps rather than as
    one designed probe suite.  All batches must agree on node count and
    probe thread count."""
    if not batches:
        raise ValueError("need at least one sample batch to concatenate")
    if len(batches) == 1:
        return batches[0]
    nodes = {b.n_nodes for b in batches}
    if len(nodes) != 1:
        raise ValueError(f"sample batches disagree on node count: {nodes}")
    shapes = {tuple(np.asarray(a).shape[1:] for a in b.wl_arrays) for b in batches}
    if len(shapes) != 1:
        raise ValueError(
            "sample batches disagree on workload shape (thread counts differ?)"
        )
    cat = lambda leaves: jnp.concatenate([jnp.asarray(a) for a in leaves])
    return CalibrationSamples(
        wl_arrays=tuple(
            cat([b.wl_arrays[i] for b in batches])
            for i in range(len(batches[0].wl_arrays))
        ),
        placements=cat([b.placements for b in batches]),
        local_read=cat([b.local_read for b in batches]),
        remote_read=cat([b.remote_read for b in batches]),
        local_write=cat([b.local_write for b in batches]),
        remote_write=cat([b.remote_write for b in batches]),
        instructions=cat([b.instructions for b in batches]),
        elapsed=cat([b.elapsed for b in batches]),
    )


def take_samples(samples: CalibrationSamples, idx) -> CalibrationSamples:
    """Row-subset a sample set (``idx`` is any numpy index expression) —
    the partial-sweep path: fitting proceeds from whatever subset of the
    probe suite a production trace happened to cover."""
    return _take_rows(samples, np.asarray(idx))


# ---------------------------------------------------------------------------
# Probe sweep design
# ---------------------------------------------------------------------------


def _spread_placement(s: int, n_threads: int) -> np.ndarray:
    counts = np.full((s,), n_threads // s, np.int32)
    counts[: n_threads % s] += 1
    return counts


def probe_suite(
    template: MachineSpec,
    n_threads: int | None = None,
    *,
    read_bpi: float = 8.0,
    write_bpi: float = 4.0,
) -> list[tuple[Workload, np.ndarray]]:
    """The designed calibration sweep: ``(workload, placement)`` pairs
    whose union of saturation patterns identifies every free parameter.

    Only the template's *structure* (node count, cores per node, issue
    rates) shapes the design — bandwidths are what the sweep measures.
    All probes share one thread count so the whole sweep stacks into a
    single vmapped trace."""
    s, cap = template.n_nodes, template.cores_per_node
    if n_threads is None:
        n_threads = min(cap, 8)
    if not 0 < n_threads <= cap:
        raise ValueError(f"{n_threads} probe threads exceed {cap} cores/node")
    nt = n_threads
    probes: list[tuple[Workload, np.ndarray]] = []

    def one_node(i: int) -> np.ndarray:
        p = np.zeros((s,), np.int32)
        p[i] = nt
        return p

    # 1. per-node local probes, one direction at a time: saturate each
    #    bank's read and write capacity in isolation.
    for i in range(s):
        for tag, rb, wb in (("r", read_bpi, 0.0), ("w", 0.0, write_bpi)):
            probes.append(
                (
                    mixed_workload(
                        f"cal-local-{tag}{i}", nt,
                        read_mix=(0.0, 1.0, 0.0), read_bpi=rb, write_bpi=wb,
                    ),
                    one_node(i),
                )
            )

    # 2. per-ordered-pair static probes: all threads on node i streaming a
    #    Static allocation on node j — saturates the (i, j) remote path
    #    (hop-attenuated) or the thinnest link on route(i, j), whichever
    #    is tighter, one direction at a time.
    for i in range(s):
        for j in range(s):
            if i == j:
                continue
            for tag, rb, wb in (("r", read_bpi, 0.0), ("w", 0.0, write_bpi)):
                probes.append(
                    (
                        mixed_workload(
                            f"cal-pair-{tag}{i}-{j}", nt,
                            read_mix=(1.0, 0.0, 0.0), read_bpi=rb,
                            write_bpi=wb, static_socket=j,
                        ),
                        one_node(i),
                    )
                )

    # 3. spread interleave stress probes: every node pumping traffic to
    #    every bank at once — the only pattern that fills fat shared links
    #    (an SNC QPI port carries both directions of 2*k^2 node pairs).
    spread = _spread_placement(s, nt)
    for tag, rb, wb in (
        ("r", read_bpi, 0.0),
        ("w", 0.0, write_bpi),
        ("rw", read_bpi, write_bpi),
    ):
        probes.append(
            (
                mixed_workload(
                    f"cal-inter-{tag}", nt,
                    read_mix=(0.0, 0.0, 0.0), read_bpi=rb, write_bpi=wb,
                ),
                spread,
            )
        )

    # 4. static-sink stress probes: every *other* node's threads
    #    converging on one bank — saturates the sink's incident links with
    #    multi-source (routed) traffic no single pair can generate.  The
    #    sink node hosts no threads (its local traffic would win a
    #    max-min share of the bank and starve the link below saturation),
    #    and several write:read ratios are swept so that for some ratio
    #    the incident link binds before either bank-direction cap does
    #    (link binds iff (R+W)/C_link exceeds both R/C_read and W/C_write
    #    — a window in W/R that depends on the capacities under test).
    for j in range(s):
        if s < 2:
            break
        others = np.zeros((s,), np.int32)
        share = _spread_placement(s - 1, nt)
        others[np.arange(s) != j] = share
        for alpha in (0.25, 0.5, 1.0):
            probes.append(
                (
                    mixed_workload(
                        f"cal-sink-{j}-a{alpha}", nt,
                        read_mix=(1.0, 0.0, 0.0), read_bpi=read_bpi,
                        write_bpi=read_bpi * alpha, static_socket=j,
                    ),
                    others,
                )
            )

    # 5. the paper's 2-run pair (§5.1): one symmetric and one asymmetric
    #    placement of a generic mixed workload — the classic seeding runs,
    #    kept in-sweep so the fit and the paper's protocol share data.
    wl_2run = mixed_workload(
        "cal-2run", nt, read_mix=(0.3, 0.3, 0.2),
        read_bpi=read_bpi * 0.5, write_bpi=write_bpi * 0.5,
    )
    probes.append((wl_2run, spread))
    probes.append(
        (wl_2run, np.asarray(asymmetric_placement(template, nt), np.int32))
    )
    return probes


@partial(
    jax.jit,
    static_argnames=("machine", "noise_std", "background_bw", "thread_classes"),
)
def _collect_jit(
    machine, wl_arrays, placements, keys, noise_std, background_bw, thread_classes
):
    def one(arrays, placement, key):
        wl = Workload("calib", *arrays)
        res = simulate(
            machine, wl, placement,
            noise_std=noise_std, background_bw=background_bw, key=key,
            thread_classes=thread_classes,
        )
        smp = res.sample
        return (
            smp.local_read, smp.remote_read, smp.local_write,
            smp.remote_write, smp.instructions,
        )

    return jax.vmap(one)(wl_arrays, placements, keys)


def collect_sweep(
    machine: MachineSpec,
    probes: Sequence[tuple[Workload, np.ndarray]] | None = None,
    *,
    noise_std: float = 0.0,
    background_bw: float = 0.0,
    key: Array | None = None,
) -> CalibrationSamples:
    """Run a probe sweep through the simulator (the synthetic-ground-truth
    path) and package the observed counters for fitting.  ``probes``
    defaults to :func:`probe_suite` on the machine itself."""
    if probes is None:
        probes = probe_suite(machine)
    wls = [wl for wl, _ in probes]
    placements = jnp.asarray(np.stack([p for _, p in probes]), jnp.int32)
    if key is None:
        key = jax.random.PRNGKey(0)
    keys = jax.random.split(key, len(wls))
    wl_arrays = _stack_probe_workloads(wls)
    lr, rr, lw, rw, ins = _collect_jit(
        machine, wl_arrays, placements, keys,
        float(noise_std), float(background_bw),
        thread_class_starts(wls),
    )
    return CalibrationSamples(
        wl_arrays=wl_arrays,
        placements=placements,
        local_read=lr, remote_read=rr, local_write=lw, remote_write=rw,
        instructions=ins,
        elapsed=jnp.ones((len(wls),), jnp.float32),
    )


# ---------------------------------------------------------------------------
# Stage 1: counter seeding
# ---------------------------------------------------------------------------


def _pair_flows(samples: CalibrationSamples, counter: Array) -> Array:
    """``(P, s, s)`` estimated source->bank flows from a bank-perspective
    counter, apportioning each bank's remote traffic to the other nodes in
    proportion to their thread counts — ``bwsig.fit``'s rule, exact when a
    single remote source is active (every pair probe; the paper's s=2)."""
    w = jax.vmap(_remote_source_weights)(samples.placements)  # (P, bank j, src i)
    return jnp.swapaxes(w * counter[:, :, None], 1, 2)  # (P, i, j)


def seed_parameters(
    template: MachineSpec,
    samples: CalibrationSamples,
    groups: LinkGroups | None = None,
    *,
    floor_frac: float = 0.02,
) -> CalibrationParams:
    """Closed-form seeds: every observed rate is a lower bound on the
    capacity it crossed, and the probe suite makes the interesting bounds
    tight.  Never exercised parameters are floored at ``floor_frac`` of
    the largest seed in their family so log-space stays finite."""
    if groups is None:
        groups = link_groups(template.topology)
    s = template.n_nodes
    el = samples.elapsed[:, None]
    lr = samples.local_read / el
    rr = samples.remote_read / el
    lw = samples.local_write / el
    rw = samples.remote_write / el

    def floored(x: Array) -> Array:
        return jnp.maximum(x, jnp.maximum(floor_frac * x.max(), 1.0))

    bank_r = floored((lr + rr).max(0))
    bank_w = floored((lw + rw).max(0))

    pair_r = _pair_flows(samples, rr)
    pair_w = _pair_flows(samples, rw)
    incidence = jnp.asarray(template.topology.route_incidence())  # (s*s, L)
    charge = jnp.matmul(
        (pair_r + pair_w).reshape(samples.placements.shape[0], s * s),
        incidence,
        precision=jax.lax.Precision.HIGHEST,  # f32 seeds, not bf16 ones
    )
    link_seed = np.asarray(floored(charge.max(0)))

    # attenuation: a multi-hop pair's flow obeys flow <= base * att**(h-1),
    # so every (flow/base)**(1/(h-1)) lower-bounds att; take the best bound
    # over pairs and directions.
    hops = np.asarray(template.topology.hop_matrix(), np.float64)
    att_seed = 0.95
    if hops.max() > 1:
        ests = []
        for base, flows in (
            (template.remote_read_bw, np.asarray(pair_r.max(0), np.float64)),
            (template.remote_write_bw, np.asarray(pair_w.max(0), np.float64)),
        ):
            multi = hops > 1
            ratio = np.clip(flows / max(base, _EPS), 1e-6, 1.0)
            ests.append((ratio ** (1.0 / np.maximum(hops - 1.0, 1.0)))[multi])
        att_seed = float(np.clip(np.concatenate(ests).max(), 0.3, 0.995))

    return CalibrationParams(
        log_link_bw=jnp.log(jnp.asarray(groups.pack(link_seed), jnp.float32)),
        log_local_read=jnp.log(bank_r.astype(jnp.float32)),
        log_local_write=jnp.log(bank_w.astype(jnp.float32)),
        att_raw=jnp.asarray(np.log(att_seed / (1.0 - att_seed)), jnp.float32),
    )


# ---------------------------------------------------------------------------
# Stage 2: projected gradient over the differentiable forward model
# ---------------------------------------------------------------------------


def _caps_from(
    template: MachineSpec, groups: LinkGroups, params: CalibrationParams
) -> Array:
    """Assemble the traced capacity vector (simulator slab order) from the
    free parameters; routing, hop counts and the remote path bases stay
    static template structure."""
    s = template.n_nodes
    link_bw = groups.unpack(jnp.exp(params.log_link_bw))
    bank_r = jnp.exp(params.log_local_read)
    bank_w = jnp.exp(params.log_local_write)
    hops = jnp.asarray(template.topology.hop_matrix(), jnp.float32)
    if template.topology.max_hops > 1:
        att = jax.nn.sigmoid(params.att_raw)
    else:  # single-hop: attenuation is structurally unobservable
        att = jnp.asarray(1.0, jnp.float32)
    extra = jnp.maximum(hops - 1.0, 0.0)
    rr = jnp.where(hops == 0, _UNUSED_CAP, template.remote_read_bw * att**extra)
    ww = jnp.where(hops == 0, _UNUSED_CAP, template.remote_write_bw * att**extra)
    return jnp.concatenate(
        [bank_r, bank_w, rr.reshape(s * s), ww.reshape(s * s), link_bw]
    )


def _residual_penalty(r: Array, huber_delta: float | None) -> Array:
    """Sum of squared residuals, or — when ``huber_delta`` is set — the
    Huber penalty: quadratic inside ``delta``, linear outside, so a few
    wildly corrupted counter rows pull the fit linearly instead of
    quadratically (the outlier-robust loss production traces need)."""
    if huber_delta is None:
        return (r**2).sum()
    a = jnp.abs(r)
    d = huber_delta
    return jnp.where(a <= d, 0.5 * a * a, d * (a - 0.5 * d)).sum()


def _sweep_loss(
    template: MachineSpec,
    groups: LinkGroups,
    samples: CalibrationSamples,
    params: CalibrationParams,
    instruction_weight: float,
    thread_classes: tuple[int, ...],
    huber_delta: float | None = None,
) -> Array:
    caps = _caps_from(template, groups, params)

    def per_sample(arrays, placement, olr, orr, olw, orw, oins, el):
        wl = Workload("calib", *arrays)
        res = simulate(
            template, wl, placement, caps=caps, thread_classes=thread_classes
        )
        smp = res.sample
        obs = jnp.concatenate([olr, orr, olw, orw]) / el
        sim = jnp.concatenate(
            [smp.local_read, smp.remote_read, smp.local_write, smp.remote_write]
        )
        total = jnp.maximum(obs.sum(), _EPS)
        err = _residual_penalty((sim - obs) / total, huber_delta)
        itot = jnp.maximum(oins.sum() / el, _EPS)
        err += instruction_weight * _residual_penalty(
            (smp.instructions - oins / el) / itot, huber_delta
        )
        return err

    errs = jax.vmap(per_sample)(
        samples.wl_arrays,
        samples.placements,
        samples.local_read,
        samples.remote_read,
        samples.local_write,
        samples.remote_write,
        samples.instructions,
        samples.elapsed,
    )
    return errs.mean()


@partial(
    jax.jit,
    static_argnames=(
        "template", "groups", "steps", "lr", "instruction_weight",
        "thread_classes", "huber_delta",
    ),
)
def _fit_jit(
    template, groups, samples, params, steps, lr, instruction_weight,
    thread_classes, huber_delta=None,
):
    schedule = adamw.cosine_schedule(
        lr, warmup_steps=min(20, max(steps // 10, 1)), total_steps=steps
    )
    # adamw.update splices its (param, m, v) work tuples back apart with
    # is_leaf=isinstance(..., tuple), so hand it a dict view of the params
    # (a NamedTuple root would itself be spliced).
    state = adamw.init(params._asdict())

    def step_fn(carry, _):
        p, st = carry
        loss, grads = jax.value_and_grad(
            lambda q: _sweep_loss(
                template, groups, samples, CalibrationParams(**q),
                instruction_weight, thread_classes, huber_delta,
            )
        )(p)
        new_p, new_st = adamw.update(
            grads, st, p, lr=schedule(st.step), weight_decay=0.0
        )
        return (new_p, new_st), loss

    (final, _), history = jax.lax.scan(
        step_fn, (params._asdict(), state), None, length=steps
    )
    final_params = CalibrationParams(**final)
    # history[k] is the loss at the PRE-update params of step k; evaluate
    # the returned params once so the reported final loss matches the
    # machine actually handed back
    final_loss = _sweep_loss(
        template, groups, samples, final_params, instruction_weight,
        thread_classes, huber_delta,
    )
    return final_params, history, final_loss


def fitted_machine(
    template: MachineSpec,
    groups: LinkGroups,
    params: CalibrationParams,
    *,
    name: str | None = None,
) -> MachineSpec:
    """Materialize a concrete, validated ``MachineSpec`` from fitted
    parameters: per-link bandwidths through :func:`topology.from_fit`
    (routes held static), per-node local tuples, scalar attenuation."""
    link_bw = np.exp(np.asarray(params.log_link_bw, np.float64))
    full_link_bw = np.asarray(groups.unpack(link_bw))
    att = (
        float(jax.nn.sigmoid(params.att_raw))
        if template.topology.max_hops > 1
        else template.hop_attenuation
    )
    machine = template._replace(
        name=name or f"{template.name}-fit",
        local_read_bw=tuple(
            float(v) for v in np.exp(np.asarray(params.log_local_read, np.float64))
        ),
        local_write_bw=tuple(
            float(v) for v in np.exp(np.asarray(params.log_local_write, np.float64))
        ),
        hop_attenuation=att,
        topology=from_fit(
            template.topology, full_link_bw, name=f"{template.topology.name}-fit"
        ),
    )
    machine.validate()
    return machine


def fit_machine(
    template: MachineSpec,
    samples: CalibrationSamples,
    *,
    steps: int = 250,
    lr: float = 0.03,
    tie_equal_bw: bool = False,
    groups: LinkGroups | None = None,
    init: CalibrationParams | None = None,
    instruction_weight: float = 0.25,
    name: str | None = None,
    clean: bool = True,
    huber_delta: float | None = None,
) -> CalibrationResult:
    """Fit a machine's free parameters from a counter sweep.

    ``template`` supplies the structure (topology link list + routes, node
    counts, core rates, remote path bases); its bandwidth values are *not*
    consulted — seeding reads them off the samples.  ``tie_equal_bw``
    shares one parameter across links the template marks as the same class
    (see :func:`repro.core.numa.topology.link_groups`).

    ``clean=True`` (default) runs :func:`clean_samples` first, so
    corrupted/non-finite counter rows are rejected (and counted in
    ``result.diagnostics``) instead of silently poisoning the AdamW fit;
    ``huber_delta`` switches the loss from squared to Huber on the
    relative residuals — the outlier-robust setting for noisy partial
    production traces (a sweep-relative delta around 0.01–0.1 works; None
    keeps the exact squared loss and bit-identical legacy fits)."""
    if samples.n_nodes != template.n_nodes:
        raise ValueError(
            f"samples cover {samples.n_nodes} nodes; template has "
            f"{template.n_nodes}"
        )
    diagnostics = None
    if clean:
        samples, diagnostics = clean_samples(samples)
    if samples.n_samples == 0:
        raise ValueError("no calibration samples to fit from")
    if groups is None:
        groups = link_groups(template.topology, tie_equal_bw=tie_equal_bw)
    if init is None:
        init = seed_parameters(template, samples, groups)
    # samples.wl_arrays are concrete here (the jit boundary is below), so
    # the static class refinement of the whole sweep is readable — this is
    # what keeps every gradient step on the grouped solver.  The last leaf
    # is the stacked static_socket scalar, whose trailing axis is samples,
    # not threads — exclude it.
    thread_classes = class_starts_from_arrays(samples.wl_arrays[:-1])
    huber = None if huber_delta is None else float(huber_delta)
    seed_loss = float(
        _sweep_loss(
            template, groups, samples, init, instruction_weight,
            thread_classes, huber,
        )
    )
    params, history, final_loss = _fit_jit(
        template, groups, samples, init, int(steps), float(lr),
        float(instruction_weight), thread_classes, huber,
    )
    return CalibrationResult(
        machine=fitted_machine(template, groups, params, name=name),
        params=params,
        groups=groups,
        loss_history=np.asarray(history),
        seed_loss=seed_loss,
        final_loss=float(final_loss),
        diagnostics=diagnostics,
    )


# ---------------------------------------------------------------------------
# Round-trip drivers and diagnostics
# ---------------------------------------------------------------------------


def blind_template(
    machine: MachineSpec,
    *,
    link_bw: float = 20.0 * GB,
    local_read_bw: float = 40.0 * GB,
    local_write_bw: float = 20.0 * GB,
    hop_attenuation: float = 1.0,
) -> MachineSpec:
    """Strip a machine of everything the calibration is supposed to
    recover, keeping only structure: link list + routes, node geometry,
    core rates and the remote path bases.  The replacement values are
    deliberately uninformative — seeding overwrites them."""
    return machine._replace(
        name=f"{machine.name}-blind",
        local_read_bw=local_read_bw,
        local_write_bw=local_write_bw,
        hop_attenuation=hop_attenuation,
        topology=from_fit(
            machine.topology,
            np.full((machine.n_links,), link_bw),
            name=f"{machine.topology.name}-blind",
        ),
    )


def fit_from_simulated(
    machine: MachineSpec,
    template: MachineSpec | None = None,
    *,
    probes: Sequence[tuple[Workload, np.ndarray]] | None = None,
    noise_std: float = 0.0,
    key: Array | None = None,
    **fit_kwargs,
) -> CalibrationResult:
    """The synthetic round trip: sweep ``machine`` (ground truth) through
    the simulator, then fit blind from the samples alone.  ``template``
    defaults to :func:`blind_template` of the machine."""
    samples = collect_sweep(machine, probes, noise_std=noise_std, key=key)
    if template is None:
        template = blind_template(machine)
    return fit_machine(template, samples, **fit_kwargs)


def counter_errors_pct(
    machine: MachineSpec, samples: CalibrationSamples
) -> np.ndarray:
    """``(P,)`` per-sample relative total-counter error (%) of
    ``machine``'s predicted counters against the observed sweep — the
    forward model replayed over the samples' workloads/placements and
    compared bank by bank.  This is the quantity the live-recalibration
    swap guard gates on: a refit spec must not *regress* it."""
    P = samples.n_samples
    if P == 0:
        raise ValueError("cannot score a machine against zero samples")
    if samples.n_nodes != machine.n_nodes:
        raise ValueError(
            f"samples cover {samples.n_nodes} nodes; machine has "
            f"{machine.n_nodes}"
        )
    keys = jax.random.split(jax.random.PRNGKey(0), P)
    thread_classes = class_starts_from_arrays(samples.wl_arrays[:-1])
    lr, rr, lw, rw, _ = _collect_jit(
        machine, samples.wl_arrays, samples.placements, keys, 0.0, 0.0,
        thread_classes,
    )
    sim = np.concatenate(
        [np.asarray(x, np.float64).reshape(P, -1) for x in (lr, rr, lw, rw)],
        axis=1,
    )
    el = np.asarray(samples.elapsed, np.float64).reshape(P, 1)
    obs = np.concatenate(
        [
            np.asarray(x, np.float64).reshape(P, -1)
            for x in (
                samples.local_read, samples.remote_read,
                samples.local_write, samples.remote_write,
            )
        ],
        axis=1,
    ) / el
    denom = np.maximum(np.abs(obs).sum(axis=1), _EPS)
    return 100.0 * np.abs(sim - obs).sum(axis=1) / denom


def sweep_median_error_pct(
    machine: MachineSpec, samples: CalibrationSamples
) -> float:
    """Median of :func:`counter_errors_pct` — the single sweep-median
    number the recalibration swap guard compares old-vs-new specs on."""
    return float(np.median(counter_errors_pct(machine, samples)))


def link_relative_errors(
    fitted: MachineSpec, reference: MachineSpec
) -> np.ndarray:
    """``(n_links,)`` relative error of every fitted link bandwidth
    against a reference machine with the same link list."""
    if fitted.topology.link_ends != reference.topology.link_ends:
        raise ValueError("machines disagree on the link list")
    fit = np.asarray(fitted.topology.link_bw, np.float64)
    ref = np.asarray(reference.topology.link_bw, np.float64)
    return np.abs(fit - ref) / ref


def local_bw_relative_errors(
    fitted: MachineSpec, reference: MachineSpec
) -> dict[str, np.ndarray]:
    """Per-node relative errors of the fitted local bandwidths."""
    out = {}
    for direction in ("read", "write"):
        fit = np.asarray(fitted.node_local_bw(direction), np.float64)
        ref = np.asarray(reference.node_local_bw(direction), np.float64)
        out[direction] = np.abs(fit - ref) / ref
    return out
