"""Production mesh construction + per-cell sharding policy.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): 16x16 = 256 chips per pod, and 2 pods = 512 chips for the
multi-pod dry-run.  The ``pod`` axis carries data parallelism across pods;
FSDP stays *inside* a pod (parameter gathers ride intra-pod ICI, only grad
all-reduce crosses the pod interconnect — see DESIGN.md §7).
"""

from __future__ import annotations

import contextlib

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.parallel import context as ctx

GB = 1 << 30

# Serving keeps params replicated over the data axis when the per-chip TP
# shard is comfortably under HBM; larger models add FSDP to serving too.
SERVE_REPLICATION_LIMIT = 6 * GB


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model code places tensors with with_sharding_constraint
    # and shard_map, which jax.make_mesh's default Explicit axes reject.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def candidate_mesh_axes(
    n_devices: int,
    *,
    axis_names: tuple[str, str] = ("data", "model"),
    min_model: int = 1,
    max_model: int | None = None,
) -> list[dict[str, int]]:
    """Every 2-axis factorization of ``n_devices`` (model axis between
    ``min_model`` and ``max_model``), in advisor candidate form — the
    enumeration ``advise_mesh_shape`` and the mesh-rank benchmark score."""
    if n_devices < 1:
        raise ValueError("need >= 1 device")
    if max_model is None:
        max_model = n_devices
    outer, inner = axis_names
    out = []
    for model in range(min_model, max_model + 1):
        if n_devices % model:
            continue
        out.append({outer: n_devices // model, inner: model})
    if not out:
        raise ValueError(
            f"no factorization of {n_devices} devices with model axis in "
            f"[{min_model}, {max_model}]"
        )
    return out


def advise_mesh_shape(
    sig,
    n_devices: int,
    *,
    chip=None,
    topology=None,
    axis_names: tuple[str, str] = ("data", "model"),
    min_model: int = 1,
    max_model: int | None = None,
):
    """Rank every 2-axis mesh factorization of ``n_devices`` by predicted
    step time through the shared advisor — scalar roofline by default, the
    routed per-link model when a
    :class:`~repro.core.meshsig.device_topology.DeviceTopology` is given.
    Returns the advisor's sorted :class:`MeshRanking` list (best first)."""
    from repro.core.meshsig.advisor import CHIP_V5E, rank_meshes

    candidates = candidate_mesh_axes(
        n_devices, axis_names=axis_names, min_model=min_model,
        max_model=max_model,
    )
    return rank_meshes(
        sig, candidates, chip=chip or CHIP_V5E, topology=topology
    )


def serve_params_replicated(cfg: ModelConfig) -> bool:
    """True when bf16 params / model-axis fit comfortably per chip."""
    tp = 16
    return cfg.param_count() * 2 / tp <= SERVE_REPLICATION_LIMIT


@contextlib.contextmanager
def cell_context(mesh, cfg: ModelConfig, shape: ShapeConfig):
    """Activate the mesh + the logical-axis policy for one (arch, shape)
    cell: decode-cache layout and the serve-time FSDP decision."""
    overrides = {}
    axis_names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in axis_names)

    if shape.kind in ("decode", "prefill"):
        if not serve_params_replicated(cfg):
            overrides["fsdp"] = ("data",)  # prefill: gathers amortized by T
        else:
            # small enough to replicate over data — dense AND expert weights
            overrides["fsdp"] = ()
            overrides["efsdp"] = ()
    if shape.kind == "decode":
        usable = [a for a in batch_axes if shape.global_batch % mesh.shape[a] == 0]
        # batch dim takes every data-ish axis it divides; the sequence dim
        # takes everything else (long_500k: batch=1 -> seq over all axes).
        cache_batch = tuple(usable) if shape.global_batch > 1 else ()
        seq_axes = tuple(a for a in axis_names if a not in cache_batch)
        overrides["cache_batch"] = cache_batch
        overrides["cache_seq"] = seq_axes
    with ctx.use_mesh(mesh), ctx.use_logical_rules(**overrides):
        yield


def serve_decode_param_shardings(mesh, cfg: ModelConfig):
    """Parameter shardings for big-model decode (§Perf iteration d2):
    dense weights shard their TP dims over model x data (2D TP), so no
    per-token FSDP weight gather ever happens — GSPMD moves the (tiny)
    partial activations instead.  Expert weights keep their data shard
    via "efsdp" (the no-gather MoE decode path).  Scoped to the PARAM
    tree only: activation constraints keep 1D TP."""
    from repro.models import model as M

    with ctx.use_logical_rules(fsdp=(), tp=("model", "data")):
        return tree_shardings(mesh, M.param_specs(cfg))


def _is_spec_leaf(x) -> bool:
    # Spec leaves are PLAIN tuples of logical dims; NamedTuples (KVCache,
    # MambaCache) are containers, not leaves.
    return type(x) is tuple


def tree_shardings(mesh, spec_tree):
    """Logical-dim tuples -> NamedShardings (leaves are tuples of dims)."""

    def to_sharding(dims):
        return NamedSharding(mesh, ctx.resolve(*dims))

    return jax.tree.map(to_sharding, spec_tree, is_leaf=_is_spec_leaf)


def batch_shardings(mesh, struct_tree):
    """Batch inputs: dim 0 over (pod, data) where divisible, else over the
    largest divisible prefix of those axes (replicated when batch=1)."""

    def sh(s):
        if not s.shape:
            return NamedSharding(mesh, P())
        b = s.shape[0]
        axes = []
        prod = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names and b % (prod * mesh.shape[a]) == 0:
                axes.append(a)
                prod *= mesh.shape[a]
        spec = P(tuple(axes) if axes else None, *([None] * (len(s.shape) - 1)))
        return NamedSharding(mesh, spec)

    return jax.tree.map(sh, struct_tree)
