"""Flash attention as a Pallas TPU kernel.

TPU adaptation of the classic GPU flash algorithm (DESIGN.md §3):

* Tiling targets the MXU/VMEM hierarchy rather than SM shared memory: the
  grid is (batch, q_head, q_block) with the KV walk as an innermost
  *arbitrary* grid dimension; (m, l, acc) live in VMEM scratch that
  persists across the KV steps of one q block (output revisiting), so the
  working set is exactly (block_q x d_head) fp32 + two (block_q,) rows.
* GQA is native: the k/v BlockSpec index maps q-head h to kv-head
  ``h // group``, so K/V tiles are fetched once per kv head — no
  ``jnp.repeat`` materialization like the XLA fallback path needs.
* block shapes default to MXU-aligned (multiples of 128 on the matmul
  dims); d_head rides whole (128 or 256 for every assigned arch).
* sliding-window / causal masking is iota-based per tile; fully-masked
  tiles short-circuit via ``pl.when`` (no MXU work issued).

Validated against ``ref.attention_ref`` in interpret mode (CPU container);
the TPU path is the compile target.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import Array
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _kernel(
    q_ref,  # (1, 1, bq, dh)
    k_ref,  # (1, 1, bkv, dh)
    v_ref,  # (1, 1, bkv, dh)
    o_ref,  # (1, 1, bq, dh)
    m_ref,  # VMEM scratch (bq,)
    l_ref,  # VMEM scratch (bq,)
    acc_ref,  # VMEM scratch (bq, dh)
    *,
    scale: float,
    causal: bool,
    window: int,
    logit_cap: float,
    block_q: int,
    block_kv: int,
    n_kv_blocks: int,
    q_offset: int,
):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = q_offset + iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    cols = ikv * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)

    # Tile-level visibility: skip tiles that the causal/window pattern
    # fully masks (saves the MXU issue entirely).
    row_min = q_offset + iq * block_q
    row_max = row_min + block_q - 1
    col_min = ikv * block_kv
    col_max = col_min + block_kv - 1
    live = True
    if causal:
        live = col_min <= row_max
    if window:
        live = jnp.logical_and(live, col_max > row_min - window)

    @pl.when(live)
    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bkv)
        if logit_cap > 0.0:
            logits = logit_cap * jnp.tanh(logits / logit_cap)
        ok = jnp.ones((block_q, block_kv), jnp.bool_)
        if causal:
            ok = jnp.logical_and(ok, cols <= rows)
        if window:
            ok = jnp.logical_and(ok, cols > rows - window)
        logits = jnp.where(ok, logits, _NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ikv == n_kv_blocks - 1)
    def finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(
    q: Array,  # (B, H, Sq, dh)
    k: Array,  # (B, Kv, Skv, dh)
    v: Array,  # (B, Kv, Skv, dh)
    *,
    causal: bool = True,
    window: int = 0,
    logit_cap: float = 0.0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = False,
) -> Array:
    B, H, Sq, dh = q.shape
    Kv, Skv = k.shape[1], k.shape[2]
    assert H % Kv == 0
    group = H // Kv
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, Skv, block_q, block_kv)
    nq, nkv = Sq // block_q, Skv // block_kv
    q_offset = Skv - Sq  # right-aligned queries (prefill continuation)

    grid = (B, H, nq, nkv)
    kernel = functools.partial(
        _kernel,
        scale=dh**-0.5,
        causal=causal,
        window=window,
        logit_cap=logit_cap,
        block_q=block_q,
        block_kv=block_kv,
        n_kv_blocks=nkv,
        q_offset=q_offset,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_kv, dh), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_kv, dh), lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
