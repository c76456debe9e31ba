"""Closed-loop sweep: one caller runs ``evaluate_batch`` over the whole
placement table for every workload of the mix, back to back, with fresh
measurement keys for each call.

The traffic file gives the workloads and the noise; the configuration
gives the host, the thread budget and the placement budget.  Each call
ends in ``block_until_ready`` on its outputs.  A seeded reservoir keeps
the outputs of ``limits["sample"]["calls"]`` calls, and after the window
the reference recomputes a seeded sample of their rows
(``bench/reference/numa.py``).
"""

from __future__ import annotations

import time

import numpy as np

from bench import sut
from bench.core import Check
from bench.reference import numa as ref
from bench.reference.noise import sweep_draws

CALL_SPAN = "sweep.evaluate_batch"
KEYS_SPAN = "sweep.keys"
STATIC_TIE_RTOL = 1e-5  # banks this close share the static-socket argmax


class Driver:
    def __init__(self, cell, seed: int, spans, seconds: float | None = None):
        self.cell = cell
        self.seed = int(seed)
        self.spans = spans
        self.calls = 0
        self.kept: list[tuple[int, object]] = []
        self.t_window = (0.0, 0.0)

    # -- set-up ---------------------------------------------------------------

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.core.numa.evaluate import evaluate_batch, sweep_placements

        cfg, tr = self.cell.config, self.cell.traffic
        self.evaluate_batch = evaluate_batch
        self.machine = sut.machine_spec(cfg)
        self.n_threads = int(cfg["n_threads"])
        self.workloads = sut.workloads(tr, self.n_threads)
        pl = cfg["placements"]
        self.placements = sweep_placements(
            self.machine, self.n_threads,
            max_placements=pl["max_placements"], seed=int(pl["sample_seed"]),
        )
        self.n_placements = int(self.placements.shape[0])
        self.n_workloads = len(self.workloads)
        self.noise_std = float(tr["noise_std"])
        self.background_bw = float(tr["background_bw"])
        self.jnp = jnp
        self.jax = jax
        # two warm calls: the first compiles (or loads the cache), the
        # second runs the signature-cache writeback path as the window does
        for call in (0, 1):
            out = self.evaluate_batch(
                self.machine, self.workloads, self.placements,
                noise_std=self.noise_std, background_bw=self.background_bw,
                keys=self._keys(call, warmup=True),
            )
            jax.block_until_ready(out)
        del out

    def _keys(self, call: int, warmup: bool = False):
        return self.jnp.asarray(sut.call_keys(self.seed, call, self.n_workloads, warmup))

    # -- window ---------------------------------------------------------------

    def window(self, seconds: float) -> None:
        k = int(self.cell.limits["sample"]["calls"])
        rng = np.random.default_rng([*sut.seed_words(self.seed), 1])
        t0 = time.perf_counter()
        t_end = t0 + seconds
        while True:
            with self.spans(KEYS_SPAN):
                keys = self._keys(self.calls)
            with self.spans(CALL_SPAN):
                out = self.evaluate_batch(
                    self.machine, self.workloads, self.placements,
                    noise_std=self.noise_std, background_bw=self.background_bw,
                    keys=keys,
                )
                self.jax.block_until_ready(out)
            # reservoir sample of k calls, drawn from the seed
            if len(self.kept) < k:
                self.kept.append((self.calls, out))
            else:
                j = int(rng.integers(0, self.calls + 1))
                if j < k:
                    self.kept[j] = (self.calls, out)
            self.calls += 1
            t1 = time.perf_counter()
            if t1 >= t_end:
                break
        self.t_window = (t0, t1)

    # -- results --------------------------------------------------------------

    @property
    def evals_per_call(self) -> int:
        return self.n_placements * self.n_workloads

    def end_to_end(self) -> dict:
        t0, t1 = self.t_window
        return {"sweep_placement_evals_per_s": self.calls * self.evals_per_call / (t1 - t0)}

    @property
    def attempted(self) -> int:
        return self.calls

    @property
    def failed(self) -> int:
        return 0

    def counters(self) -> dict:
        return {
            "calls": self.calls,
            "evals_per_call": self.evals_per_call,
            "placements": self.n_placements,
            "workloads": self.n_workloads,
        }

    def loadgen(self) -> dict:
        return {}

    def placements_host(self) -> np.ndarray:
        return np.asarray(self.placements)

    def release(self) -> None:
        """Pull the kept calls' outputs to the host and drop every device
        array this driver holds."""
        self.kept = [(call, to_host(out)) for call, out in self.kept]
        self.placements_np = self.placements_host()
        self.placements = None
        self.workloads = None

    def close(self) -> None:
        pass

    # -- the comparison with the plain reference -------------------------------

    def check(self) -> list[Check]:
        """The numbers the cell's limits file names, each beside its limit."""
        readings = compare(self.cell, self.seed, self.kept, self.placements_np)
        return [Check(k, readings[k], float(v)) for k, v in self.cell.limits["limits"].items()]


SIGS = ("read", "write", "combined")
FRACTIONS = (("static", "static_fraction"), ("local", "local_fraction"),
             ("per_thread", "per_thread_fraction"))


def to_host(out) -> dict:
    """The rows of one ``evaluate_batch`` output the check compares, and
    each workload's fitted read, write and combined signature."""
    host = {
        "total_bw": np.asarray(out.total_bw),
        "errors_read": np.asarray(out.errors_read),
        "errors_write": np.asarray(out.errors_write),
        "errors_combined": np.asarray(out.errors_combined),
    }
    for name, d in zip(SIGS, (out.signatures.read, out.signatures.write,
                              out.combined_signatures.read)):
        host[f"bank_{name}"] = np.asarray(d.static_socket)
        for key, field in FRACTIONS:
            host[f"{key}_{name}"] = np.asarray(getattr(d, field))
    return host


def compare(cell, seed, kept, program_rows, ar=ref.REFERENCE, sample=None):
    """Widest gaps between the kept calls and the reference:
    ``table_rows``, the program's placement rows that are not the
    reference's own table (``ref.placement_table``); ``sig_abs``, the
    absolute gap in any fitted signature fraction (static, local,
    per-thread; read, write and combined) of any workload; and over a
    seeded sample of the reference's placements, ``bw_rel``, the relative
    gap in run bandwidth, and ``err_abs``, the absolute gap in any counter
    error (a fraction of run bandwidth).  ``bw_bias`` is the median of the
    signed relative gaps in run bandwidth, and ``bw_dev`` the widest
    distance of one from that median: on the chip ``jnp.exp`` in the
    program's noise factor reads ~1.2e-06 low, which shifts every row
    alike, and ``bw_dev`` is the part of the gap that it leaves out.

    With ``ar=CONTROL`` the rows are the control's own, computed in lower
    precision, and compared with the float64 reference the same way."""
    cfg, tr = cell.config, cell.traffic
    machine = ref.machine_from_config(cfg["machine"])
    placements, table_rows = ref.placement_table(cfg, program_rows)
    n = int(cfg["n_threads"])
    std, bg = float(tr["noise_std"]), float(tr["background_bw"])
    size = sample or int(cell.limits["sample"]["placements"])
    rng = np.random.default_rng([*sut.seed_words(seed), 2])
    bw, err, sig = [], [], []
    for call, out in kept:
        keys = sut.call_keys(seed, call, len(tr["workloads"]))
        rows = np.sort(rng.choice(placements.shape[0], size=min(size, placements.shape[0]), replace=False))
        p = placements[rows]
        for i, w in enumerate(tr["workloads"]):
            wl = ref.workload_arrays(w, n)
            z = sweep_draws(keys[i], machine.s, placements.shape[0])
            sigs, same_banks = _reference_signatures(machine, wl, z, std, out, i)
            tot, er, ew, ec = ref.sweep_rows(machine, wl, sigs, p, z["read"][rows], z["write"][rows], std, bg)
            if ar is ref.REFERENCE:
                got = (out["total_bw"][i][rows], out["errors_read"][i][rows],
                       out["errors_write"][i][rows], out["errors_combined"][i][rows])
                got_sigs = [{key: float(out[f"{key}_{name}"][i]) for key, _ in FRACTIONS} for name in SIGS]
            else:
                got_sigs, got = _control_rows(machine, wl, z, std, bg, p, rows, ar)
            bw.append((got[0] - tot) / tot)
            for g, r in zip(got[1:], (er, ew, ec)):
                err.append(np.abs(g - r).ravel())
            for g, r in zip(got_sigs, sigs):
                sig.append(np.asarray([abs(g[key] - r[key]) for key, _ in FRACTIONS]))
            if ar is ref.REFERENCE and not same_banks:
                sig.append(np.ones(1))  # a static bank off the tie set
    bw, err, sig = (np.concatenate(x) for x in (bw, err, sig))
    bias = float(np.median(bw))
    return {
        "table_rows": table_rows,
        "bw_rel": float(np.abs(bw).max()),
        "bw_bias": bias,
        "bw_dev": float(np.abs(bw - bias).max()),
        "err_abs": float(err.max()),
        "sig_abs": float(sig.max()),
    }


def _reference_signatures(machine, wl, z, std, out, i):
    """float64 read, write and combined signatures of one workload, and
    whether the program's static bank was one the reference allows: where
    the argmax is tied to rounding, the tied bank the program chose is
    taken (either choice is exact)."""
    sym, asym = ref.profile(machine, wl, z, std)
    sigs, same = [], True
    for d, (a, b), chosen in (
        ("read", (sym, asym), out["bank_read"][i]),
        ("write", (sym, asym), out["bank_write"][i]),
        ("read", (ref.combine(sym), ref.combine(asym)), out["bank_combined"][i]),
    ):  # the order of SIGS
        tied = ref.static_candidates(a, d, STATIC_TIE_RTOL)
        same = same and int(chosen) in tied
        sigs.append(ref.fit_direction(a, b, d, int(chosen) if int(chosen) in tied else None))
    return sigs, same


def _control_rows(machine, wl, z, std, bg, p, rows, ar):
    sym, asym = ref.profile(machine, wl, z, std, ar)
    sigs = [
        ref.fit_direction(sym, asym, "read"),
        ref.fit_direction(sym, asym, "write"),
        ref.fit_direction(ref.combine(sym), ref.combine(asym), "read"),
    ]
    return sigs, ref.sweep_rows(machine, wl, sigs, p, z["read"][rows], z["write"][rows], std, bg, ar=ar)
