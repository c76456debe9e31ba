import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Mesh-signature validation — the paper's §6.2.2 accuracy experiment in
the mesh domain.

Profile two compilations (symmetric 16x16, asymmetric 32x8), fit the
signature, predict the per-axis collective link bytes of UNSEEN mesh
aspects, then actually compile those meshes and measure.  Errors are
reported the paper's way: |predicted - measured| as a percentage of the
run's total link traffic, plus the advisor's ranking quality.

Run as a script (needs its own process: 512 host devices):
    PYTHONPATH=src python -m repro.core.meshsig.validate --arch llama3-8b
"""

import argparse
import json
import time
from pathlib import Path

import jax
from jax.sharding import AxisType

from repro.configs.base import SHAPES, get_config
from repro.core.meshsig.advisor import CHIP_V5E, ChipSpec, rank_meshes
from repro.core.meshsig.fit import (
    MeshProfile,
    MeshSignature,
    fit_mesh_signature,
    profile_from_analysis,
)
from repro.core.meshsig.hlo_counters import analyze_hlo
from repro.launch import mesh as mesh_lib

RESULTS = Path(__file__).resolve().parents[4] / "benchmarks" / "dryrun_results"

# Adaptation finding (EXPERIMENTS.md §Mesh-signature): unlike the NUMA
# domain, a *symmetric* mesh profile cannot attribute group-size-k
# collectives to an axis when both axes have size k, so BOTH profiling
# compilations are asymmetric (they play the roles of the paper's two
# runs: two placements that jointly identify every signature parameter).
FIT_MESHES = [{"data": 32, "model": 8}, {"data": 64, "model": 4}]
VAL_MESHES = [{"data": 8, "model": 32}, {"data": 4, "model": 64}, {"data": 16, "model": 16}]


def measured_axis_bytes(prof: MeshProfile) -> dict[str, float]:
    """Collapse a profile's (class, axis) link bytes to per-axis totals —
    the measured counterpart of ``sig.predict_axis_bytes``."""
    meas = {a: 0.0 for a in prof.axis_sizes}
    for (_, a), v in prof.class_axis_bytes.items():
        meas[a] += v
    return meas


def prediction_errors(
    sig: MeshSignature, axes: dict[str, int], meas: dict[str, float]
) -> dict[str, float]:
    """Per-axis |predicted - measured| as % of the run's total link
    traffic (the paper's §6.2.2 metric).  Distinct axis sizes attribute
    measurements exactly; a symmetric mesh only identifies the total."""
    pred = sig.predict_axis_bytes(axes)
    total = sum(meas.values()) or 1.0
    if len(set(axes.values())) == len(axes):
        return {a: abs(pred.get(a, 0.0) - meas[a]) / total * 100 for a in axes}
    return {"total": abs(sum(pred.values()) - total) / total * 100}


def profile_mesh(cfg, shape, axes: dict) -> tuple[MeshProfile, float]:
    from repro.launch.dryrun import lower_cell  # sets the same XLA_FLAGS

    mesh = jax.make_mesh(
        tuple(axes.values()), tuple(axes.keys()),
        axis_types=(AxisType.Auto,) * len(axes),
    )
    t0 = time.time()
    with mesh_lib.cell_context(mesh, cfg, shape):
        jitted, args, _ = lower_cell(cfg, shape, mesh)
        compiled = jitted.lower(*args).compile()
    analysis = analyze_hlo(compiled.as_text())
    return profile_from_analysis(analysis, axes), time.time() - t0


def run_validation(
    arch: str = "llama3-8b",
    shape_name: str = "train_4k",
    *,
    chip: ChipSpec = CHIP_V5E,
) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]

    profiles: dict[str, MeshProfile] = {}
    record: dict = {"arch": arch, "shape": shape_name, "meshes": {}}

    sym, t_sym = profile_mesh(cfg, shape, FIT_MESHES[0])
    asym, t_asym = profile_mesh(cfg, shape, FIT_MESHES[1])
    sig = fit_mesh_signature(sym, asym)
    record["fit_compile_s"] = round(t_sym + t_asym, 1)
    record["class_fractions"] = sig.class_fractions()
    record["terms"] = {
        f"{cls}/{axis}": {"beta": beta, "e": e}
        for (cls, axis), (beta, e) in sig.terms.items()
    }

    errors = []
    actual_times = {}
    for axes in VAL_MESHES:
        name = "x".join(str(v) for v in axes.values())
        try:
            prof, t = profile_mesh(cfg, shape, axes)
        except Exception as e:  # a candidate may be un-compilable; record it
            record["meshes"][name] = {"error": str(e)[:300]}
            continue
        pred = sig.predict_axis_bytes(axes)
        meas = measured_axis_bytes(prof)
        mesh_errs = prediction_errors(sig, axes, meas)
        errors.extend(mesh_errs.values())
        actual_times[name] = sum(meas.values())
        record["meshes"][name] = {
            "predicted_axis_bytes": pred,
            "measured_axis_bytes": meas,
            "error_pct_of_total": mesh_errs,
            "compile_s": round(t, 1),
        }

    errors.sort()
    record["median_error_pct"] = errors[len(errors) // 2] if errors else None
    record["max_error_pct"] = errors[-1] if errors else None

    # Advisor ranking vs measured total link bytes on the validation meshes
    rankings = rank_meshes(sig, VAL_MESHES, chip=chip)
    record["advisor_order"] = [
        "x".join(str(v) for v in r.axis_sizes.values()) for r in rankings
    ]
    record["measured_order"] = sorted(actual_times, key=actual_times.get)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--shape", default="train_4k")
    args = ap.parse_args()
    rec = run_validation(args.arch, args.shape)
    RESULTS.mkdir(parents=True, exist_ok=True)
    out = RESULTS / f"meshsig_validation__{args.arch}__{args.shape}.json"
    out.write_text(json.dumps(rec, indent=1, default=str))
    print(json.dumps({k: rec[k] for k in (
        "arch", "shape", "class_fractions", "median_error_pct",
        "max_error_pct", "advisor_order", "measured_order") if k in rec},
        indent=1, default=str))


if __name__ == "__main__":
    main()
