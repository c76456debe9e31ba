"""Selective scan (mamba-1) as a Pallas TPU kernel.

TPU adaptation (DESIGN.md §3): the CUDA kernel's per-thread sequential
recurrence becomes a channel-tiled VMEM-resident scan:

* grid = (batch, d_inner blocks, seq chunks); the chunk dimension is
  *arbitrary* (sequential) and the (block_d, N) state lives in VMEM
  scratch, persisting across chunks — the state never round-trips HBM
  within a sequence;
* channels are independent, so the d_inner grid dimension is embarrassingly
  parallel (and TP shards it across chips before the kernel is entered);
* per chunk, the inputs are (chunk, block_d) tiles — VPU elementwise work
  with an (N)-wide inner broadcast; N = 16 for every assigned SSM arch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    dt_ref,  # (1, c, bd)
    a_ref,  # (bd, N)
    b_ref,  # (1, c, N)
    c_ref,  # (1, c, N)
    x_ref,  # (1, c, bd)
    y_ref,  # (1, c, bd)
    h_ref,  # VMEM scratch (bd, N) — persists across chunk steps
    *,
    chunk: int,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...]  # (bd, N)

    def step(t, h):
        dt_t = dt_ref[0, t, :]  # (bd,)
        x_t = x_ref[0, t, :]
        b_t = b_ref[0, t, :]  # (N,)
        c_t = c_ref[0, t, :]
        da = jnp.exp(dt_t[:, None] * a)  # (bd, N)
        h = h * da + (dt_t * x_t)[:, None] * b_t[None, :]
        y_ref[0, t, :] = (h * c_t[None, :]).sum(axis=1)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def selective_scan(
    dt: Array,  # (B, S, di) f32
    a: Array,  # (di, N) f32
    b: Array,  # (B, S, N) f32
    c: Array,  # (B, S, N) f32
    x: Array,  # (B, S, di) f32
    *,
    block_d: int = 512,
    chunk: int = 256,
    interpret: bool = False,
) -> Array:
    B, S, di = x.shape
    n = a.shape[1]
    block_d = min(block_d, di)
    chunk = min(chunk, S)
    assert di % block_d == 0 and S % chunk == 0, (di, block_d, S, chunk)
    grid = (B, di // block_d, S // chunk)

    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda bi, d, j: (bi, j, d)),
            pl.BlockSpec((block_d, n), lambda bi, d, j: (d, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, d, j: (bi, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda bi, d, j: (bi, j, 0)),
            pl.BlockSpec((1, chunk, block_d), lambda bi, d, j: (bi, j, d)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d), lambda bi, d, j: (bi, j, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(dt, a, b, c, x)
