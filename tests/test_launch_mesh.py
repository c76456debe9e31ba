"""Direct coverage for ``launch/mesh.py`` (candidate enumeration + the
advisor entry point + sharding policy helpers) and the pure error-metric
helpers of ``meshsig/validate.py``."""

import jax
import jax.numpy as jnp
import pytest

from repro.core.meshsig.fit import MeshProfile, class_factor, fit_mesh_signature
from repro.launch import mesh as mesh_lib


def synth_profile(axes, *, grad_bytes=1e9, gather_bytes=5e8, a2a_base=2e9):
    b = axes.get("data", 1) * axes.get("pod", 1)
    kd, km = axes["data"], axes["model"]
    out = {
        ("interleaved", "data"): class_factor("interleaved", kd) * grad_bytes,
        ("static", "data"): class_factor("static", kd) * gather_bytes,
        ("per_shard", "model"): class_factor("per_shard", km) * a2a_base / b,
    }
    return MeshProfile(
        axis_sizes=dict(axes),
        class_axis_bytes=out,
        local_bytes=1e10 / b,
        flops=1e13 / b,
    )


def fitted_sig():
    return fit_mesh_signature(
        synth_profile({"data": 8, "model": 2}),
        synth_profile({"data": 4, "model": 4}),
    )


# ---------------------------------------------------------------------------
# candidate_mesh_axes
# ---------------------------------------------------------------------------


def test_candidate_mesh_axes_enumerates_factorizations():
    cands = mesh_lib.candidate_mesh_axes(16)
    assert cands == [
        {"data": 16, "model": 1},
        {"data": 8, "model": 2},
        {"data": 4, "model": 4},
        {"data": 2, "model": 8},
        {"data": 1, "model": 16},
    ]
    # dict key order is the advisor's embedding order: outer axis first
    assert all(list(c) == ["data", "model"] for c in cands)


def test_candidate_mesh_axes_bounds_and_names():
    cands = mesh_lib.candidate_mesh_axes(
        12, axis_names=("pod", "model"), min_model=2, max_model=6
    )
    assert cands == [
        {"pod": 6, "model": 2},
        {"pod": 4, "model": 3},
        {"pod": 3, "model": 4},
        {"pod": 2, "model": 6},
    ]


def test_candidate_mesh_axes_raises_when_empty():
    with pytest.raises(ValueError, match="no factorization"):
        mesh_lib.candidate_mesh_axes(7, min_model=2, max_model=6)
    with pytest.raises(ValueError, match=">= 1 device"):
        mesh_lib.candidate_mesh_axes(0)


# ---------------------------------------------------------------------------
# advise_mesh_shape
# ---------------------------------------------------------------------------


def test_advise_mesh_shape_scalar_and_routed_agree_on_fc():
    from repro.core.meshsig.advisor import CHIP_V5E
    from repro.core.meshsig.device_topology import nvlink_island

    sig = fitted_sig()
    scalar = mesh_lib.advise_mesh_shape(sig, 16)
    routed = mesh_lib.advise_mesh_shape(
        sig, 16, topology=nvlink_island(16, CHIP_V5E.ici_bw)
    )
    assert len(scalar) == 5
    assert scalar[0].step_s <= scalar[-1].step_s
    assert [r.axis_sizes for r in scalar] == [r.axis_sizes for r in routed]
    assert routed[0].step_s == pytest.approx(scalar[0].step_s, rel=1e-9)


def test_advise_mesh_shape_chip_override_scales_compute():
    from repro.core.meshsig.advisor import CHIP_V5E, CHIP_V5P

    sig = fitted_sig()
    v5e = mesh_lib.advise_mesh_shape(sig, 16, chip=CHIP_V5E)
    v5p = mesh_lib.advise_mesh_shape(sig, 16, chip=CHIP_V5P)
    by_axes = {tuple(r.axis_sizes.items()): r for r in v5e}
    for r in v5p:
        e = by_axes[tuple(r.axis_sizes.items())]
        assert r.compute_s == pytest.approx(
            e.compute_s * CHIP_V5E.peak_flops / CHIP_V5P.peak_flops
        )


# ---------------------------------------------------------------------------
# Sharding policy helpers
# ---------------------------------------------------------------------------


def test_serve_params_replicated_threshold(monkeypatch):
    from repro.configs.base import get_config

    cfg = get_config("llama3-8b")  # 8B bf16 / 16-way TP ~ 1 GB << 6 GB
    assert mesh_lib.serve_params_replicated(cfg)
    monkeypatch.setattr(mesh_lib, "SERVE_REPLICATION_LIMIT", 1)
    assert not mesh_lib.serve_params_replicated(cfg)


def test_batch_shardings_divisibility():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    tree = {
        "tokens": jax.ShapeDtypeStruct((4, 128), jnp.int32),
        "scalar": jax.ShapeDtypeStruct((), jnp.float32),
    }
    sh = mesh_lib.batch_shardings(mesh, tree)
    # PartitionSpec normalizes a one-axis tuple entry ("data",) to "data"
    assert sh["tokens"].spec[0] == "data"  # 4 % 1 == 0 -> data axis
    assert sh["scalar"].spec == jax.sharding.PartitionSpec()


# ---------------------------------------------------------------------------
# meshsig/validate.py pure helpers
# ---------------------------------------------------------------------------


def _validate_module():
    # validate.py sets XLA_FLAGS for its own __main__ use; initialize the
    # backend first so importing it cannot re-shape this process's devices
    jax.devices()
    from repro.core.meshsig import validate

    return validate


def test_measured_axis_bytes_collapses_classes():
    validate = _validate_module()
    prof = MeshProfile(
        axis_sizes={"data": 4, "model": 2},
        class_axis_bytes={
            ("interleaved", "data"): 6.0,
            ("static", "data"): 2.0,
            ("per_shard", "model"): 3.0,
        },
        local_bytes=0.0,
        flops=0.0,
    )
    assert validate.measured_axis_bytes(prof) == {"data": 8.0, "model": 3.0}


def test_prediction_errors_distinct_and_symmetric():
    validate = _validate_module()
    sig = fitted_sig()
    # distinct sizes: exact per-axis attribution -> perfect prediction
    axes = {"data": 8, "model": 2}
    meas = validate.measured_axis_bytes(synth_profile(axes))
    errs = validate.prediction_errors(sig, axes, meas)
    assert set(errs) == {"data", "model"}
    assert max(errs.values()) < 1e-6
    # symmetric sizes: only the total is identified
    axes = {"data": 4, "model": 4}
    meas = validate.measured_axis_bytes(synth_profile(axes))
    errs = validate.prediction_errors(sig, axes, meas)
    assert set(errs) == {"total"}
    assert errs["total"] < 1e-6
    # a deliberately-wrong measurement shows up as % of total traffic
    errs = validate.prediction_errors(
        sig, axes, {a: v * 2 for a, v in meas.items()}
    )
    assert errs["total"] == pytest.approx(50.0, rel=1e-3)
