"""Jit'd public wrapper for the flash-attention kernel.

Chooses MXU-aligned block sizes from the problem shape and exposes the
same (B, S, H, dh) layout the model layer uses.  ``interpret=True`` runs
the kernel through the Pallas interpreter (the CPU test path); the default
compiles it for the TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import Array

from repro.kernels.flash_attention.kernel import flash_attention


def _pick_block(s: int, target: int = 512) -> int:
    b = min(target, s)
    while s % b:
        b //= 2
    return max(b, 1)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "logit_cap", "interpret")
)
def mha_flash(
    q: Array,  # (B, Sq, H, dh) — model layout
    k: Array,  # (B, Skv, Kv, dh)
    v: Array,
    *,
    causal: bool = True,
    window: int = 0,
    logit_cap: float = 0.0,
    interpret: bool = False,
) -> Array:
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention(
        qt,
        kt,
        vt,
        causal=causal,
        window=window,
        logit_cap=logit_cap,
        block_q=_pick_block(q.shape[1]),
        block_kv=_pick_block(k.shape[1]),
        interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3)
