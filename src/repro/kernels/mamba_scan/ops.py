"""Jit'd public wrapper for the selective-scan kernel.  ``interpret=True``
runs it through the Pallas interpreter (the CPU test path); the default
compiles it for the TPU."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array

from repro.kernels.mamba_scan.kernel import selective_scan


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_scan(
    dt: Array,
    a: Array,
    b: Array,
    c: Array,
    x: Array,
    *,
    interpret: bool = False,
) -> Array:
    block_d = 512
    di = x.shape[-1]
    while di % block_d:
        block_d //= 2
    chunk = 256
    while x.shape[1] % chunk:
        chunk //= 2
    return selective_scan(
        dt.astype(jnp.float32),
        a.astype(jnp.float32),
        b.astype(jnp.float32),
        c.astype(jnp.float32),
        x.astype(jnp.float32),
        block_d=block_d,
        chunk=chunk,
        interpret=interpret,
    )
