"""Plain reference for the NUMA advisor: the max-min-fair bandwidth fill,
the paper's 2-run signature fit and the counter-error tail, per thread,
in float64 NumPy.

It follows the semantics the system documents (arXiv:2106.08026 §4-§6
and the simulator's progressive filling) and is written from the
configuration and traffic files alone: it imports nothing of the program
and takes no table the program has built.  Every contraction goes through
``Arith.dot``, so the same code computed with a coarser ``dot`` is the
lower-precision control (:data:`CONTROL`).

Shapes: ``M`` rows (placements), ``n`` threads, ``s`` NUMA nodes, ``R``
resources in slab order (bank reads s, bank writes s, remote read paths
s*s, remote write paths s*s, links L).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import ml_dtypes
import numpy as np

EPS = 1e-12  # the fill's "uses a resource" threshold (bytes/s)
FIT_EPS = 1e-20  # the fit's division guard
TIE_RTOL = 1e-6  # resources within this of the bottleneck freeze together


@dataclass(frozen=True)
class Arith:
    """The arithmetic a reference run uses: element type and contraction."""

    dtype: type
    dot: Callable  # einsum(subscripts, a, b) in ``dtype``
    name: str


def _einsum64(subscripts, a, b):
    return np.einsum(subscripts, a, b, optimize=True)


def _bf16x3(subscripts, a, b):
    """A float32 contraction in three bfloat16 passes (the TPU's ``high``
    precision): each operand splits into a bfloat16 head and a bfloat16
    tail, and the tail-times-tail pass is dropped."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    a_hi = a.astype(ml_dtypes.bfloat16).astype(np.float32)
    b_hi = b.astype(ml_dtypes.bfloat16).astype(np.float32)
    a_lo = (a - a_hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    b_lo = (b - b_hi).astype(ml_dtypes.bfloat16).astype(np.float32)
    out = np.einsum(subscripts, a_hi, b_hi, optimize=True)
    out = out + np.einsum(subscripts, a_hi, b_lo, optimize=True)
    out = out + np.einsum(subscripts, a_lo, b_hi, optimize=True)
    return out.astype(np.float32)


REFERENCE = Arith(np.float64, _einsum64, "float64")
CONTROL = Arith(np.float32, _bf16x3, "float32-high")


# ---------------------------------------------------------------------------
# Machine and workloads, from the configuration and traffic files
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Machine:
    s: int  # NUMA nodes
    cap: int  # cores per node
    core_rate: np.ndarray  # (s,) instructions/s per thread at full speed
    caps: np.ndarray  # (R,) capacities in slab order
    incidence: np.ndarray  # (s*s, L) 1 where the pair's route crosses link l


def machine_from_config(m: dict) -> Machine:
    s = int(m["sockets"]) * int(m.get("nodes_per_socket", 1))
    cap = int(m["cores_per_socket"]) // int(m.get("nodes_per_socket", 1))
    links = [(int(a), int(b)) for a, b, _ in m["links"]]
    link_bw = np.asarray([float(bw) for _, _, bw in m["links"]])
    index = {frozenset(e): l for l, e in enumerate(links)}
    paths = {(p[0], p[-1]): p for p in m.get("multi_hop_paths", [])}
    incidence = np.zeros((s * s, len(links)))
    hops = np.zeros((s, s))
    for i in range(s):
        for j in range(s):
            if i == j:
                continue
            path = paths.get((i, j), [i, j])
            for a, b in zip(path[:-1], path[1:]):
                incidence[i * s + j, index[frozenset((a, b))]] = 1.0
            hops[i, j] = len(path) - 1
    att = float(m.get("hop_attenuation", 1.0)) ** np.maximum(hops - 1.0, 0.0)
    remote_r = np.where(hops == 0, np.inf, float(m["remote_read_bw"]) * att)
    remote_w = np.where(hops == 0, np.inf, float(m["remote_write_bw"]) * att)
    caps = np.concatenate(
        [
            np.full(s, float(m["local_read_bw"])),
            np.full(s, float(m["local_write_bw"])),
            remote_r.reshape(-1),
            remote_w.reshape(-1),
            link_bw,
        ]
    )
    return Machine(
        s=s,
        cap=cap,
        core_rate=np.full(s, float(m["core_rate"])),
        caps=caps,
        incidence=incidence,
    )


FIELDS = (
    "read_static", "read_local", "read_per_thread",
    "write_static", "write_local", "write_per_thread",
    "read_bpi", "write_bpi",
)


def workload_arrays(w: dict, n: int) -> dict:
    """Per-thread ground-truth arrays of one traffic-file workload: a
    uniform mix, or a Page-rank-like ``violator`` whose first
    ``hot_fraction`` of threads lean harder on the static region."""
    ones = np.ones(n)
    if "violator" in w:
        v = w["violator"]
        hot = (np.arange(n) < np.round(v["hot_fraction"] * n)).astype(float)
        rs, rl, rp = v["base_read_mix"]
        r_static = ones * rs + hot * v["hot_extra_static"]
        r_local = ones * rl * (1.0 - hot * 0.5)
        r_pt = ones * rp * (1.0 - hot * 0.5)
        scale = np.minimum(1.0, 1.0 / np.maximum(r_static + r_local + r_pt, 1e-9))
        ws, wl, wp = v["write_mix"]
        out = {
            "read_static": r_static * scale,
            "read_local": r_local * scale,
            "read_per_thread": r_pt * scale,
            "write_static": ones * ws,
            "write_local": ones * wl,
            "write_per_thread": ones * wp,
            "read_bpi": ones * v["read_bpi"] * (1.0 + hot * (v["hot_intensity"] - 1.0)),
            "write_bpi": ones * v["write_bpi"],
        }
    else:
        rm, wm = w["read_mix"], w["write_mix"]
        out = {
            "read_static": ones * rm[0],
            "read_local": ones * rm[1],
            "read_per_thread": ones * rm[2],
            "write_static": ones * wm[0],
            "write_local": ones * wm[1],
            "write_per_thread": ones * wm[2],
            "read_bpi": ones * w["read_bpi"],
            "write_bpi": ones * w["write_bpi"],
        }
    out["static_socket"] = int(w.get("static_socket", 0))
    return out


def placement_space(s: int, cap: int, n: int) -> np.ndarray:
    """Every placement of ``n`` threads, one per core, over ``s`` nodes of
    ``cap`` cores (paper §6.2.2), in lexicographic order: node 0's count
    ascending."""
    out = []

    def rec(prefix, left, slots):
        if slots == 1:
            if left <= cap:
                out.append(prefix + [left])
            return
        for k in range(min(cap, left) + 1):
            if left - k <= cap * (slots - 1):
                rec(prefix + [k], left - k, slots - 1)

    rec([], n, s)
    return np.asarray(out, np.int64)


def space_size(s: int, cap: int, n: int) -> int:
    """How many placements :func:`placement_space` holds, counted without
    listing them."""
    ways = np.zeros(n + 1, dtype=object)
    ways[0] = 1
    for _ in range(s):
        ways = np.asarray([sum(ways[m - k] for k in range(min(cap, m) + 1)) for m in range(n + 1)],
                          dtype=object)
    return int(ways[n])


def placement_table(cfg: dict, program_rows: np.ndarray) -> tuple[np.ndarray, int]:
    """The placement table the reference evaluates, from the configuration,
    and how many of the program's rows are not as it says.

    Where the configuration sweeps the whole space, the table is
    :func:`placement_space` and a program row counts when it differs from
    the row at its index.  Where it samples ``max_placements`` of a larger
    space, the sample is the program's: a row counts when it is no
    placement of the space or repeats an earlier one, and each row missing
    from or beyond the configured count counts too."""
    m = cfg["machine"]
    s = int(m["sockets"]) * int(m.get("nodes_per_socket", 1))
    cap = int(m["cores_per_socket"]) // int(m.get("nodes_per_socket", 1))
    n = int(cfg["n_threads"])
    rows = np.asarray(program_rows, np.int64).reshape(-1, s) if np.size(program_rows) else np.zeros((0, s), np.int64)
    total = space_size(s, cap, n)
    budget = cfg["placements"]["max_placements"]
    if budget is None or int(budget) >= total:
        table = placement_space(s, cap, n)
        k = min(len(rows), len(table))
        wrong = int(np.any(rows[:k] != table[:k], axis=1).sum()) + abs(len(rows) - len(table))
        return table, wrong
    valid = (rows.sum(axis=1) == n) & np.all((rows >= 0) & (rows <= cap), axis=1)
    seen, repeats = set(), 0
    for r in map(tuple, rows):
        repeats += r in seen
        seen.add(r)
    return rows, int((~valid).sum()) + repeats + abs(len(rows) - int(budget))


# ---------------------------------------------------------------------------
# Ground truth: per-thread progressive filling
# ---------------------------------------------------------------------------


def thread_nodes(placements: np.ndarray, n: int) -> np.ndarray:
    """(M, n) node of each thread: the first p_0 threads on node 0, the
    next p_1 on node 1, and so on."""
    bounds = np.cumsum(placements, axis=1)
    t = np.arange(n)
    return (t[None, :, None] >= bounds[:, None, :]).sum(axis=2)


def _unit_demand(machine, wl, placements, onehot, direction, ar):
    """(M, n, s) bytes/s a thread moves to each bank at full speed."""
    d = direction
    s = machine.s
    pf = placements.astype(ar.dtype)
    pt_row = pf / np.maximum(pf.sum(axis=1, keepdims=True), 1.0)
    used = (pf > 0).astype(ar.dtype)
    il_row = used / np.maximum(used.sum(axis=1, keepdims=True), 1.0)
    static_row = (np.arange(s) == wl["static_socket"]).astype(ar.dtype)
    st = wl[f"{d}_static"].astype(ar.dtype)
    lo = wl[f"{d}_local"].astype(ar.dtype)
    pt = wl[f"{d}_per_thread"].astype(ar.dtype)
    inter = 1.0 - st - lo - pt
    mix = (
        st[None, :, None] * static_row[None, None, :]
        + lo[None, :, None] * onehot
        + pt[None, :, None] * pt_row[:, None, :]
        + inter[None, :, None] * il_row[:, None, :]
    )
    rate = machine.core_rate.astype(ar.dtype)[np.argmax(onehot, axis=2)]
    bpi = wl[f"{d}_bpi"].astype(ar.dtype)
    return (rate * bpi[None, :])[:, :, None] * mix


def progressive_fill(usage, caps, ar):
    """Max-min fair rates of every thread: all active threads grow
    together until a resource saturates; the threads using it freeze."""
    M, n, R = usage.shape
    x = np.zeros((M, n), ar.dtype)
    frozen = np.zeros((M, n), bool)
    for _ in range(min(n, R) + 1):
        if frozen.all():
            break
        active = ~frozen
        frozen_usage = ar.dot("mn,mnr->mr", np.where(frozen, x, 0.0), usage)
        act_usage = ar.dot("mn,mnr->mr", active.astype(ar.dtype), usage)
        resid = np.maximum(caps[None, :] - frozen_usage, 0.0)
        with np.errstate(divide="ignore", invalid="ignore"):
            lam = np.where(
                act_usage > EPS, resid / np.maximum(act_usage, EPS), np.inf
            )
        lam_star = np.minimum(lam.min(axis=1), 1.0)
        bottleneck = lam <= lam_star[:, None] * (1.0 + TIE_RTOL)
        uses = ar.dot("mnr,mr->mn", usage, bottleneck.astype(ar.dtype)) > EPS
        freeze = active & (uses | (lam_star >= 1.0)[:, None])
        x = np.where(freeze, lam_star[:, None], x).astype(ar.dtype)
        frozen = frozen | freeze
    return np.where(frozen, x, 1.0).astype(ar.dtype)


def simulate(machine: Machine, wl: dict, placements, ar: Arith = REFERENCE):
    """Noise-free steady state of ``wl`` at each placement: per-node
    ``(M, s, s)`` read and write flows (CPU node -> bank) and ``(M, s)``
    instructions per second."""
    placements = np.asarray(placements, np.int64).reshape(-1, machine.s)
    s = machine.s
    n = wl["read_bpi"].shape[0]
    node = thread_nodes(placements, n)
    onehot = (node[:, :, None] == np.arange(s)).astype(ar.dtype)  # (M, n, s)
    read_u = _unit_demand(machine, wl, placements, onehot, "read", ar)
    write_u = _unit_demand(machine, wl, placements, onehot, "write", ar)
    off = (1.0 - np.eye(s)).astype(ar.dtype)
    rr = onehot[:, :, :, None] * read_u[:, :, None, :] * off
    ww = onehot[:, :, :, None] * write_u[:, :, None, :] * off
    M = placements.shape[0]
    links = ar.dot(
        "mnp,pl->mnl", (rr + ww).reshape(M, n, s * s),
        machine.incidence.astype(ar.dtype),
    )
    usage = np.concatenate(
        [read_u, write_u, rr.reshape(M, n, s * s), ww.reshape(M, n, s * s), links],
        axis=2,
    )
    rates = progressive_fill(usage, machine.caps.astype(ar.dtype), ar)
    weighted = onehot * rates[:, :, None]
    read_flows = ar.dot("mti,mtj->mij", weighted, read_u)
    write_flows = ar.dot("mti,mtj->mij", weighted, write_u)
    thread_rate = machine.core_rate.astype(ar.dtype)[node]
    instructions = ar.dot("mti,mt->mi", weighted, thread_rate)
    return read_flows, write_flows, instructions


def objective(machine, wl, placements, ar: Arith = REFERENCE):
    """Work rate (instructions/s, the quantity the advisor maximises) and
    total bytes/s moved, for each placement."""
    rf, wf, ins = simulate(machine, wl, placements, ar)
    return ins.sum(axis=1), rf.sum(axis=(1, 2)) + wf.sum(axis=(1, 2))


# ---------------------------------------------------------------------------
# The paper's 2-run profile and signature fit (§5)
# ---------------------------------------------------------------------------


def symmetric_placement(machine: Machine, n: int) -> np.ndarray:
    return np.full(machine.s, n // machine.s, np.int64)


def asymmetric_placement(machine: Machine, n: int) -> np.ndarray:
    """Same thread count, unequal split: node 0 takes the feasible count
    nearest 3/4 of the threads (ties to the heavier), the rest spread
    evenly with overflow spilled rightward."""
    s, cap = machine.s, machine.cap
    target = -(-3 * n // 4)

    def split_for(first):
        rest = n - first
        if rest < 0 or rest > (s - 1) * cap:
            return None
        others = [rest // (s - 1)] * (s - 1)
        others[0] += rest - sum(others)
        for k in range(s - 2):
            if others[k] > cap:
                others[k + 1] += others[k] - cap
                others[k] = cap
        counts = [first] + others
        return counts if max(counts) <= cap else None

    fallback = None
    for first in sorted(range(min(cap, n) + 1), key=lambda f: (abs(f - target), -f)):
        counts = split_for(first)
        if counts is None:
            continue
        if len(set(counts)) > 1:
            return np.asarray(counts, np.int64)
        fallback = fallback or counts
    return np.asarray(fallback, np.int64)


def counters(read_flows, write_flows, instructions, placement):
    """Bank-perspective counters of one run (paper §2.1)."""
    lr = np.diagonal(read_flows)
    lw = np.diagonal(write_flows)
    return {
        "local_read": lr,
        "remote_read": read_flows.sum(axis=0) - lr,
        "local_write": lw,
        "remote_write": write_flows.sum(axis=0) - lw,
        "instructions": instructions,
        "n": np.asarray(placement, read_flows.dtype),
    }


def _normalize(c, d):
    n = c["n"]
    rate = np.where(n > 0, c["instructions"] / np.maximum(n, FIT_EPS), 1.0)
    w = (1.0 - np.eye(n.shape[0])) * n[None, :]
    w = w / np.maximum(w.sum(axis=1, keepdims=True), FIT_EPS)
    local = c[f"local_{d}"] / np.maximum(rate, FIT_EPS)
    remote = (w * c[f"remote_{d}"][:, None] / np.maximum(rate[None, :], FIT_EPS)).sum(axis=1)
    return local, remote, w, n


def static_candidates(sym_c, d, rtol):
    """Banks whose symmetric-run total lies within ``rtol`` of the largest:
    the static socket's argmax is ambiguous among them."""
    local, remote, _, _ = _normalize(sym_c, d)
    totals = local + remote
    return np.flatnonzero(totals >= totals.max() * (1.0 - rtol))


def fit_direction(sym_c, asym_c, d, static_socket=None):
    """The four properties of one direction (static socket and the static,
    local and per-thread fractions).  ``static_socket`` overrides the
    argmax, for banks tied to rounding (see :func:`static_candidates`)."""
    local, remote, _, _ = _normalize(sym_c, d)
    s = local.shape[0]
    totals = local + remote
    ss = int(np.argmax(totals)) if static_socket is None else int(static_socket)
    peak = totals[ss]
    total = max(totals.sum(), FIT_EPS)
    sf = float(np.clip((peak - (totals.sum() - peak) / max(s - 1, 1)) / total, 0.0, 1.0))

    onehot = (np.arange(s) == ss).astype(local.dtype)
    st_total = sf * total
    loc = np.maximum(np.where(onehot, local - st_total / s, local), 0.0)
    rem = np.maximum(np.where(onehot, remote - st_total * (s - 1) / s, remote), 0.0)
    r = (rem / np.maximum(loc + rem, FIT_EPS)).mean()
    lf = float(np.clip((1.0 - r * s / (s - 1)) * (1.0 - sf), 0.0, 1.0 - sf))

    local, remote, w, n = _normalize(asym_c, d)
    per_cpu = local + (w * remote[:, None]).sum(axis=0)
    remote = np.where(onehot, remote - sf * ((1.0 - onehot) * per_cpu).sum(), remote)
    local = np.where(onehot, local - sf * (onehot * per_cpu).sum(), local)
    local = np.maximum(local - lf * per_cpu, 0.0)
    remote = np.maximum(remote, 0.0)
    from_cpu = (w * remote[:, None]).sum(axis=0)
    l_meas = local / np.maximum(local + from_cpu, FIT_EPS)
    used = (n > 0).astype(n.dtype)
    pt_e = n / max(n.sum(), FIT_EPS)
    il_e = used / max(used.sum(), 1.0)
    act = used * (local + from_cpu > FIT_EPS)
    dx = (pt_e - il_e) * act
    dy = (l_meas - il_e) * act
    p = float(np.clip((dx * dy).sum() / max((dx * dx).sum(), FIT_EPS), 0.0, 1.0))
    pf = float(np.clip(p * (1.0 - lf - sf), 0.0, 1.0))
    return {"static_socket": ss, "static": sf, "local": lf, "per_thread": pf}


def combine(c):
    """Reads and writes merged into the read slots (paper §6.2.1)."""
    out = dict(c)
    out["local_read"] = c["local_read"] + c["local_write"]
    out["remote_read"] = c["remote_read"] + c["remote_write"]
    out["local_write"] = np.zeros_like(c["local_write"])
    out["remote_write"] = np.zeros_like(c["remote_write"])
    return out


def profile(machine, wl, noise, std, ar: Arith = REFERENCE):
    """The two profiling runs' counters.  ``noise`` holds the standard
    normal draws of each run, ``{"sym"|"asym": (read (s,s), write (s,s),
    instructions (s,))}``; ``std`` is the lognormal noise scale."""
    n = wl["read_bpi"].shape[0]
    out = {}
    for run, p in (
        ("sym", symmetric_placement(machine, n)),
        ("asym", asymmetric_placement(machine, n)),
    ):
        rf, wf, ins = simulate(machine, wl, p[None, :], ar)
        zr, zw, zi = (np.asarray(z, ar.dtype) for z in noise[run])
        rf = rf[0] * np.exp(std * zr)
        wf = wf[0] * np.exp(std * zw)
        ins = ins[0] * np.exp(0.2 * std * zi)
        out[run] = counters(rf, wf, ins, p)
    return out["sym"], out["asym"]


# ---------------------------------------------------------------------------
# The §6.2 counter-error tail over a placement batch
# ---------------------------------------------------------------------------


def direction_errors(sig, placements, flows):
    """|predicted - measured| bank counters (local then remote) for one
    direction's signature, from the measured ``(M, s, s)`` flows."""
    M, s, _ = flows.shape
    p = placements.astype(flows.dtype)
    used = (p > 0).astype(flows.dtype)
    pt = p / np.maximum(p.sum(axis=1, keepdims=True), 1.0)
    il = used / np.maximum(used.sum(axis=1, keepdims=True), 1.0)
    inter = min(max(1.0 - sig["static"] - sig["local"] - sig["per_thread"], 0.0), 1.0)
    st = (np.arange(s) == sig["static_socket"]).astype(flows.dtype)
    demand = flows.sum(axis=2)  # (M, s) bytes/s issued by each node's CPUs
    # predicted[i, j] = demand_i * (static*st_j + local*[i==j] + pt*pt_j
    #                               + inter*used_i*il_j)
    pred = demand[:, :, None] * (
        sig["static"] * st[None, None, :]
        + sig["local"] * np.eye(s, dtype=flows.dtype)[None, :, :]
        + sig["per_thread"] * pt[:, None, :]
        + inter * used[:, :, None] * il[:, None, :]
    )
    p_local = np.diagonal(pred, axis1=1, axis2=2)
    p_remote = pred.sum(axis=1) - p_local
    m_local = np.diagonal(flows, axis1=1, axis2=2)
    m_remote = flows.sum(axis=1) - m_local
    return np.concatenate(
        [np.abs(p_local - m_local), np.abs(p_remote - m_remote)], axis=1
    )


def sweep_rows(machine, wl, sigs, placements, z_read, z_write, std, background=0.0,
               ar: Arith = REFERENCE):
    """One workload's rows of ``evaluate_batch``: run bandwidth and the
    read, write and combined counter errors as fractions of it.
    ``sigs = (read, write, combined)`` direction signatures; ``z_*`` the
    ``(M, s, s)`` standard normal measurement draws."""
    s = machine.s
    rf, wf, _ = simulate(machine, wl, placements, ar)
    rf = rf * np.exp(std * np.asarray(z_read, ar.dtype)) + background / (s * s)
    wf = wf * np.exp(std * np.asarray(z_write, ar.dtype)) + background / (s * s)
    totals = np.maximum(rf.sum(axis=(1, 2)) + wf.sum(axis=(1, 2)), 1e-9)
    inv = 1.0 / totals[:, None]
    e_read = inv * direction_errors(sigs[0], placements, rf)
    e_write = inv * direction_errors(sigs[1], placements, wf)
    e_comb = inv * direction_errors(sigs[2], placements, rf + wf)
    return totals, e_read, e_write, e_comb
