"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus a detail block per bench to
stderr-like comment lines).  Figures 1-18 drive the NUMA simulator +
signature engine; the mesh-domain rows read the dry-run artifacts
(generated by ``repro.launch.dryrun`` / ``repro.core.meshsig.validate``,
which need their own 512-device processes).
"""

from __future__ import annotations

import json
import time
from pathlib import Path

RESULTS = Path(__file__).resolve().parent / "dryrun_results"


def _mesh_prediction():
    """Mesh-domain signature accuracy (beyond-paper §6.2.2 analogue).
    Derived: worst per-arch median link-byte prediction error % on unseen
    meshes; details include the advisor ranking quality per arch."""
    recs = sorted(RESULTS.glob("meshsig_validation__*.json"))
    if not recs:
        return float("nan"), {"note": "run repro.core.meshsig.validate first"}
    details = {}
    worst_median = 0.0
    for p in recs:
        rec = json.loads(p.read_text())
        worst_median = max(worst_median, rec.get("median_error_pct") or 0.0)
        details[rec["arch"]] = {
            "median_error_pct": rec.get("median_error_pct"),
            "advisor_ranking_correct": rec.get("advisor_order")
            == rec.get("measured_order"),
            "class_fractions": rec.get("class_fractions"),
        }
    return worst_median, details


def _dryrun_matrix():
    """Dry-run deliverable summary.  Derived: number of failed cells
    (must be 0)."""
    cells = [
        json.loads(p.read_text())
        for p in RESULTS.glob("*__*.json")
        if not p.name.startswith("meshsig")
    ]
    by_status: dict[str, int] = {}
    for c in cells:
        by_status[c.get("status", "?")] = by_status.get(c.get("status", "?"), 0) + 1
    return by_status.get("failed", 0), {"cells": by_status}


def _placement_sweep():
    """Multi-socket batched sweep throughput.  Derived: placements/sec
    through the jitted ``evaluate_batch`` trace on the 4-socket preset
    (details carry the median model error %)."""
    from benchmarks.placement_sweep import numa_placement_sweep

    return numa_placement_sweep()


def _roofline_summary():
    """Roofline headline.  Derived: median useful-fraction % across
    single-pod train cells (see EXPERIMENTS.md for the full table)."""
    import numpy as np

    from benchmarks.roofline import analyze

    rows = [r for r in analyze("single") if r["shape"] == "train_4k"]
    if not rows:
        return float("nan"), {}
    med = float(np.median([r["useful_fraction"] for r in rows])) * 100
    worst = min(rows, key=lambda r: r["useful_fraction"])
    return med, {"worst_train_cell": f"{worst['arch']} ({100*worst['useful_fraction']:.2f}%)"}


def main() -> None:
    from benchmarks import figures

    benches = [
        ("fig01_placement_speedups", figures.fig01_placement_speedups),
        ("fig02_machine_bandwidths", figures.fig02_machine_bandwidths),
        ("fig05_worked_example", figures.fig05_worked_example),
        ("fig12_synthetic_signatures", figures.fig12_synthetic_signatures),
        ("fig13_15_stability", figures.fig13_15_stability),
        ("fig16_misfit_detection", figures.fig16_misfit_detection),
        ("fig17_accuracy_cdf", figures.fig17_accuracy_cdf),
        ("fig18_error_vs_bandwidth", figures.fig18_error_vs_bandwidth),
        ("numa_placement_sweep", _placement_sweep),
        ("mesh_prediction", _mesh_prediction),
        ("dryrun_matrix", _dryrun_matrix),
        ("roofline_summary", _roofline_summary),
    ]
    print("name,us_per_call,derived")
    for name, fn in benches:
        # a bench that raises ends the run with a non-zero exit
        t0 = time.time()
        derived, details = fn()
        us = (time.time() - t0) * 1e6
        print(f"{name},{us:.0f},{derived}")
        if details:
            print(f"#   {json.dumps(details, default=str)[:400]}")


if __name__ == "__main__":
    main()
