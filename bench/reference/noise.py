"""The measurement-noise draws a sweep call consumes, re-derived from its
PRNG keys (the keys are the benchmark's input; ``evaluate_batch``
documents how it splits them).

Per workload key: ``prof, meas = split(key)``; the profiling runs split
``prof`` into ``sym, asym`` and each of those three ways into read, write
and instruction draws; ``meas`` splits into the read and write draws over
the whole ``(P, s, s)`` flow batch.  Drawn on the host CPU when JAX has
one, so the check leaves the chip alone.
"""

from __future__ import annotations

import jax
import numpy as np


def _cpu():
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def sweep_draws(key: np.ndarray, s: int, n_placements: int) -> dict:
    """Standard normal draws of one workload's fit and measurement."""
    device = _cpu()
    with jax.default_device(device) if device is not None else _null():
        k = jax.numpy.asarray(np.asarray(key, np.uint32))
        k_prof, k_meas = jax.random.split(k)
        out = {}
        for run, kr in zip(("sym", "asym"), jax.random.split(k_prof)):
            k1, k2, k3 = jax.random.split(kr, 3)
            out[run] = (
                np.asarray(jax.random.normal(k1, (s, s)), np.float64),
                np.asarray(jax.random.normal(k2, (s, s)), np.float64),
                np.asarray(jax.random.normal(k3, (s,)), np.float64),
            )
        kr, kw = jax.random.split(k_meas)
        out["read"] = np.asarray(jax.random.normal(kr, (n_placements, s, s)), np.float64)
        out["write"] = np.asarray(jax.random.normal(kw, (n_placements, s, s)), np.float64)
    return out


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
