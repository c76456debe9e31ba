"""Gradient compression: int8 ring exchange with error feedback.

Distributed-optimization trick (DESIGN.md §7): the data-parallel gradient
reduction is the largest recurring collective in training (the paper's
*Interleaved* class — ring traffic spread evenly over the axis).  Replacing
the fp32 all-reduce with an int8 reduce-scatter + all-gather cuts its link
bytes ~4x:

    all-reduce fp32 ring:  2 * (k-1)/k * 4B per element
    int8 RS + int8 AG:     2 * (k-1)/k * 1B per element (+ scales)

Quantization is per-tensor symmetric with an **error-feedback residual**
(the caller carries it between steps), which keeps SGD convergence — the
quantization error is re-injected next step instead of being lost.

Implemented with explicit ``shard_map`` collectives so the byte reduction
is visible to the HLO counters (and to real ICI).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import Array
from jax.sharding import PartitionSpec as P

from repro.parallel import context as ctx


def _quantize(x: Array) -> tuple[Array, Array]:
    scale = jnp.maximum(jnp.abs(x).max(), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum_mean(x: Array, axis_names: tuple[str, ...]) -> Array:
    """Mean over ``axis_names`` of an fp32 tensor using int8 wire format.

    Must be called inside shard_map.  Implementation: int8 reduce-scatter
    (via all-to-all on the flattened tensor) -> local fp32 sum -> int8
    all-gather.
    """
    k = 1
    for a in axis_names:
        k *= jax.lax.axis_size(a)
    if k == 1:
        return x
    shape = x.shape
    n = x.size
    pad = (-n) % k
    flat = jnp.pad(x.reshape(-1), (0, pad))
    chunks = flat.reshape(k, (n + pad) // k)

    q, scale = _quantize(chunks)
    # reduce-scatter: each member ends with the sum of its chunk
    axis = axis_names[0] if len(axis_names) == 1 else axis_names
    swapped = jax.lax.all_to_all(q[:, None], axis, split_axis=0, concat_axis=1)
    scales = jax.lax.all_gather(scale, axis)
    # swapped: (1, k, chunk) int8 — dequantize each peer's contribution
    parts = swapped[0].astype(jnp.float32) * scales[:, None]
    local_sum = parts.sum(axis=0)  # fp32 sum of my chunk
    q2, scale2 = _quantize(local_sum)
    gathered = jax.lax.all_gather(q2, axis)  # (k, chunk) int8
    scales2 = jax.lax.all_gather(scale2, axis)
    full = (gathered.astype(jnp.float32) * scales2[:, None]).reshape(-1)
    out = full[:n].reshape(shape)
    return out / k


def compressed_grad_mean(
    grads: Any, residual: Any | None = None
) -> tuple[Any, Any]:
    """Error-feedback compressed data-parallel gradient mean.

    ``grads`` are batch-sharded (already averaged within each shard's
    microbatch); this averages them across the data axes with int8 wire
    traffic.  Returns (mean_grads, new_residual).  With no active mesh this
    is the identity (single host).
    """
    mesh = ctx.current_mesh()
    axes = ctx.physical_axes("dp_all")
    if mesh is None or not axes:
        return grads, residual

    if residual is None:
        residual = jax.tree.map(lambda g: jnp.zeros_like(g, jnp.float32), grads)

    def one(g, r):
        def body(gb, rb):
            with_fb = gb.astype(jnp.float32) + rb
            reduced = compressed_psum_mean(with_fb, axes)
            new_r = with_fb - reduced  # local quantization error, re-injected
            return reduced.astype(gb.dtype), new_r

        spec = P()  # grads enter replicated per dp shard group
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(spec, spec),
            out_specs=(spec, spec),
            check_vma=False,
        )(g, r)

    pairs = jax.tree.map(one, grads, residual)
    mean = jax.tree.map(lambda t: t[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
    new_res = jax.tree.map(lambda t: t[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
    return mean, new_res
