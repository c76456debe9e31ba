"""Mixture-of-Experts FFN with explicit expert-parallel sharding.

Distribution scheme (hardware adaptation — see DESIGN.md §3):

* Expert weights are sharded over the ``model`` mesh axis.  When the expert
  count is smaller than the axis (mixtral: 8 < 16) each expert is *split*
  along ``d_ff`` into ``factor = axis/E`` slices, so the stacked weight
  tensor always has ``E * factor`` shard-able rows and every chip holds
  expert work.  The factor slices produce partial sums that the combine
  psum adds back together.
* Expert weights are additionally FSDP-sharded over ``data`` on the
  ``d_model`` dim and all-gathered per layer inside the shard_map body
  (ZeRO-3 semantics, overlappable by the scheduler).
* Activations enter batch-sharded and model-replicated; each chip
  dispatches its local tokens to its local experts with a capacity-bounded
  scatter (no giant GShard one-hot dispatch tensors), and a single psum
  over ``model`` performs the combine.  In the paper's taxonomy the
  expert-parallel traffic is the **per-thread** class — it follows shard
  ownership — which is exactly why the MoE cells are the
  paper-representative dry-run cells.

With no active mesh the same code runs single-device (smoke tests).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import Array
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.parallel import context as ctx


def moe_factor(cfg: ModelConfig) -> int:
    """d_ff split factor so experts fill the whole model axis."""
    axis = ctx.axis_size("expert")
    if axis <= cfg.n_experts:
        assert cfg.n_experts % max(axis, 1) == 0, (cfg.n_experts, axis)
        return 1
    assert axis % cfg.n_experts == 0, (cfg.n_experts, axis)
    factor = axis // cfg.n_experts
    assert cfg.d_ff % factor == 0, (cfg.d_ff, factor)
    return factor


def init_moe_params(key: Array, cfg: ModelConfig, dtype) -> dict:
    """Weights stored pre-split: (E * factor, d_model, d_ff / factor), so
    the expert axis always fills the model mesh axis with no runtime
    reshuffle of sharded tensors."""
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    factor = moe_factor(cfg)
    rows, f_loc = e * factor, f // factor
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = d**-0.5
    return {
        "router": (jax.random.normal(k1, (d, e)) * scale).astype(jnp.float32),
        "w_gate": (jax.random.normal(k2, (rows, d, f_loc)) * scale).astype(dtype),
        "w_up": (jax.random.normal(k3, (rows, d, f_loc)) * scale).astype(dtype),
        "w_down": (jax.random.normal(k4, (rows, f_loc, d)) * f**-0.5).astype(dtype),
    }


def moe_param_specs(cfg: ModelConfig) -> dict:
    # "efsdp" (not "fsdp") so serve-mode remaps of the dense weights leave
    # expert weights data-sharded — a 398B MoE cannot replicate them.
    return {
        "router": (None, None),
        "w_gate": ("expert", "efsdp", None),
        "w_up": ("expert", "efsdp", None),
        "w_down": ("expert", None, "efsdp"),
    }


def _capacity(cfg: ModelConfig, tokens: int) -> int:
    c = math.ceil(cfg.capacity_factor * tokens * cfg.experts_per_token / cfg.n_experts)
    return max(4, min(c, tokens))


def _local_moe(
    cfg: ModelConfig,
    x: Array,  # (T, D) local tokens
    router: Array,  # (D, E)
    w_gate: Array,  # (E_loc, D, F_loc) — this chip's expert slices
    w_up: Array,
    w_down: Array,  # (E_loc, F_loc, D)
    first_expert: Array,  # scalar: global slot id of local slice row 0
    factor: int,
) -> tuple[Array, Array]:
    """Dispatch local tokens to local expert slices; returns the *partial*
    combine (this chip's experts only) plus the load-balancing aux loss."""
    T, D = x.shape
    e_loc = w_gate.shape[0]
    k = cfg.experts_per_token
    C = _capacity(cfg, T)

    logits = (x.astype(jnp.float32)) @ router  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)  # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # Load-balance aux (Switch-style): E * sum_e f_e * p_e.
    me = probs.mean(axis=0)  # (E,)
    ce = jnp.zeros((cfg.n_experts,)).at[top_i.reshape(-1)].add(1.0) / (T * k)
    aux = cfg.n_experts * jnp.sum(me * ce)

    # §Perf iteration c2: combine in the compute dtype.  Multiplying bf16
    # expert outputs by the f32 gate promoted every expert-matmul cotangent
    # AND the shard_map input cotangent's psum to f32 — the dominant
    # all-reduce of the MoE train cells.  Gate precision is preserved in
    # the f32 routing math; only the combine product is bf16.
    out = jnp.zeros((T, D), x.dtype)
    for s in range(e_loc):
        expert_id = (first_expert + s) // factor  # global expert this slot serves
        sel = (top_i == expert_id).astype(jnp.float32)  # (T, k)
        gate = (sel * top_p).sum(axis=-1)  # combine weight per token
        mask = gate > 0.0
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1  # position within expert
        keep = mask & (pos < C)
        slot = jnp.where(keep, pos, C)  # C = overflow bin

        buf = jnp.zeros((C + 1, D), x.dtype).at[slot].add(
            jnp.where(keep[:, None], x, 0.0)
        )
        h = jax.nn.silu(buf @ w_gate[s]) * (buf @ w_up[s])  # (C+1, F_loc)
        y = h @ w_down[s]  # (C+1, D) — partial over d_ff when factor > 1
        out = out + jnp.where(
            keep[:, None], y[slot] * gate.astype(y.dtype)[:, None], 0.0
        )
    return out, aux


def moe_ffn_a2a(cfg: ModelConfig, p: dict, x: Array) -> tuple[Array, Array]:
    """True expert parallelism with all-to-all dispatch (beyond-paper
    extension; see EXPERIMENTS.md §Perf cell c).

    Tokens enter sequence-sharded over the ``model`` axis (each chip
    routes only its S/16 slice — no duplicated dispatch compute), are
    exchanged with a capacity-bounded ``all_to_all`` to the chips owning
    their experts (gates ride along as payload), processed, and exchanged
    back.  In the paper's taxonomy this moves the MoE traffic from the
    Interleaved class (the gather-EP psum ring) into the **Per-thread**
    class — traffic proportional to shard ownership — which is exactly the
    class split the mesh signature's asymmetric profiling identifies.

    Requires factor == 1 (experts >= model axis): qwen3 (128e), jamba (16e).
    """
    mesh = ctx.current_mesh()
    B, S, D = x.shape
    assert moe_factor(cfg) == 1, "a2a path needs n_experts >= model axis"
    if mesh is None:
        return moe_ffn(cfg, p, x)  # single device: same math, no exchange

    batch_axes = ctx.divisible_batch_axes(B) or None
    fsdp_axes = ctx.physical_axes("efsdp")
    ep_axis = ctx.physical_axes("expert")[0]
    n_shards = mesh.shape[ep_axis]
    e_loc = cfg.n_experts // n_shards
    assert S % n_shards == 0, (S, n_shards)
    k = cfg.experts_per_token

    def body(xb, router, wg, wu, wd):
        if fsdp_axes:
            wg = jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_axes, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_axes, axis=2, tiled=True)
        bl, sl, dl = xb.shape
        t_loc = bl * sl
        xt = xb.reshape(t_loc, dl)
        # local routing of the local token slice only
        logits = xt.astype(jnp.float32) @ router  # (T_loc, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=0)
        ce = jnp.zeros((cfg.n_experts,)).at[top_i.reshape(-1)].add(1.0) / (t_loc * k)
        aux = cfg.n_experts * jnp.sum(me * ce)

        # per destination shard: which tokens go there + their local-expert gates
        c_send = max(4, math.ceil(cfg.capacity_factor * t_loc * k / n_shards))
        send = jnp.zeros((n_shards, c_send, dl + e_loc), xb.dtype)
        slots = []
        for j in range(n_shards):
            on_j = (top_i // e_loc) == j  # (T_loc, k)
            gates = jnp.zeros((t_loc, e_loc), jnp.float32)
            gates = gates.at[
                jnp.arange(t_loc)[:, None], jnp.where(on_j, top_i % e_loc, 0)
            ].add(jnp.where(on_j, top_p, 0.0))
            mask = on_j.any(axis=1)
            pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
            keep = mask & (pos < c_send)
            slot = jnp.where(keep, pos, c_send - 1)
            payload = jnp.concatenate([xt, gates.astype(xb.dtype)], axis=1)
            send = send.at[j, slot].add(
                jnp.where(keep[:, None], payload, 0.0)
            )
            slots.append((slot, keep))

        recv = jax.lax.all_to_all(
            send[:, None], ep_axis, split_axis=0, concat_axis=0
        )[:, 0].reshape(n_shards * c_send, dl + e_loc)
        rx, rgates = recv[:, :dl], recv[:, dl:].astype(jnp.float32)

        # second-level local dispatch: received tokens -> this chip's
        # experts via the same capacity-bounded scatter (no dense waste)
        r_tokens = n_shards * c_send
        c2 = max(4, math.ceil(cfg.capacity_factor * r_tokens / e_loc))
        y = jnp.zeros((r_tokens, dl), xb.dtype)
        for e in range(e_loc):
            gate_e = rgates[:, e]
            mask = gate_e > 0.0
            pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
            keep = mask & (pos < c2)
            slot = jnp.where(keep, pos, c2)
            buf = jnp.zeros((c2 + 1, dl), xb.dtype).at[slot].add(
                jnp.where(keep[:, None], rx, 0.0)
            )
            h = jax.nn.silu(buf @ wg[e]) * (buf @ wu[e])
            ye = h @ wd[e]
            y = y + jnp.where(
                keep[:, None], ye[slot] * gate_e[:, None].astype(xb.dtype), 0.0
            )

        back = jax.lax.all_to_all(
            y.reshape(n_shards, c_send, dl)[:, None],
            ep_axis,
            split_axis=0,
            concat_axis=0,
        )[:, 0]  # (n_shards, c_send, D): slice j = my tokens' outputs from shard j
        out = jnp.zeros((t_loc, dl), xb.dtype)
        for j, (slot, keep) in enumerate(slots):
            out = out + jnp.where(keep[:, None], back[j][slot], 0.0)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        aux = jax.lax.pmean(aux, ep_axis)
        return out.reshape(bl, sl, dl), aux

    seq_sharded = jax.lax.with_sharding_constraint(
        x,
        jax.sharding.NamedSharding(
            mesh, P(batch_axes, ep_axis, None)
        ),
    )
    fsdp_spec = fsdp_axes[0] if len(fsdp_axes) == 1 else (fsdp_axes or None)
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch_axes, ep_axis, None),
            P(None, None),
            P(ep_axis, fsdp_spec, None),
            P(ep_axis, fsdp_spec, None),
            P(ep_axis, None, fsdp_spec),
        ),
        out_specs=(P(batch_axes, ep_axis, None), P()),
        check_vma=False,
    )(seq_sharded, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    out = jax.lax.with_sharding_constraint(
        out, jax.sharding.NamedSharding(mesh, P(batch_axes, None, None))
    )
    return out, aux


def _local_moe_sharded_weights(
    cfg: ModelConfig,
    x: Array,  # (T, D) — T is tiny (decode)
    router: Array,
    w_gate: Array,  # (E_loc, D/f, F_loc) — FSDP shard, NOT gathered
    w_up: Array,
    w_down: Array,  # (E_loc, F_loc, D/f)
    first_expert: Array,
    factor: int,
    fsdp_axes: tuple[str, ...],
) -> tuple[Array, Array]:
    """Decode-time expert compute against FSDP weight shards (§Perf d1):
    at one token per sequence, gathering expert weights moves GBs to
    multiply KBs.  Instead contract the local D-slice, psum the (tiny)
    (C, F) partials, and finish with a tiny activation all-gather — zero
    weight movement.  The paper's placement insight inverted: move the
    data to the memory, not the memory to the data."""
    T, D = x.shape
    e_loc = w_gate.shape[0]
    k = cfg.experts_per_token
    C = _capacity(cfg, T)
    n_f = 1
    for a in fsdp_axes:
        n_f *= jax.lax.axis_size(a)
    d_loc = D // n_f
    idx = jax.lax.axis_index(fsdp_axes)

    logits = (x.astype(jnp.float32)) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    me = probs.mean(axis=0)
    ce = jnp.zeros((cfg.n_experts,)).at[top_i.reshape(-1)].add(1.0) / (T * k)
    aux = cfg.n_experts * jnp.sum(me * ce)

    out = jnp.zeros((T, D), x.dtype)
    for s in range(e_loc):
        expert_id = (first_expert + s) // factor
        sel = (top_i == expert_id).astype(jnp.float32)
        gate = (sel * top_p).sum(axis=-1)
        mask = gate > 0.0
        pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
        keep = mask & (pos < C)
        slot = jnp.where(keep, pos, C)
        buf = jnp.zeros((C + 1, D), x.dtype).at[slot].add(
            jnp.where(keep[:, None], x, 0.0)
        )
        buf_slice = jax.lax.dynamic_slice_in_dim(buf, idx * d_loc, d_loc, 1)
        h = jax.nn.silu(
            jax.lax.psum(buf_slice @ w_gate[s], fsdp_axes)
        ) * jax.lax.psum(buf_slice @ w_up[s], fsdp_axes)  # (C+1, F_loc)
        y_slice = h @ w_down[s]  # (C+1, D/f)
        y = jax.lax.all_gather(y_slice, fsdp_axes, axis=1, tiled=True)
        out = out + jnp.where(
            keep[:, None], y[slot] * gate.astype(y.dtype)[:, None], 0.0
        )
    return out, aux


def moe_ffn(
    cfg: ModelConfig, p: dict, x: Array, *, decode: bool = False
) -> tuple[Array, Array]:
    """MoE FFN over (B, S, D) activations. Returns (out, aux_loss)."""
    mesh = ctx.current_mesh()
    B, S, D = x.shape
    factor = moe_factor(cfg)

    if mesh is None:  # single-device path (smoke tests)
        out, aux = _local_moe(
            cfg,
            x.reshape(B * S, D),
            p["router"],
            p["w_gate"],
            p["w_up"],
            p["w_down"],
            jnp.asarray(0, jnp.int32),
            factor,
        )
        return out.reshape(B, S, D).astype(x.dtype), aux

    batch_axes = ctx.divisible_batch_axes(B) or None
    fsdp_axes = ctx.physical_axes("efsdp")
    ep_axis = ctx.physical_axes("expert")[0]
    e_loc = cfg.n_experts * factor // mesh.shape[ep_axis]
    if decode and fsdp_axes:
        # The no-gather path contracts weight D-shards along the fsdp axes
        # and psums the partials — every fsdp shard must therefore hold the
        # SAME tokens.  Replicating the decode batch costs a ~MB gather of
        # activations vs the GBs of weight gathers it removes.
        batch_axes = tuple(
            a
            for a in (batch_axes if isinstance(batch_axes, tuple) else
                      ((batch_axes,) if batch_axes else ()))
            if a not in fsdp_axes
        ) or None

    def body(xb, router, wg, wu, wd):
        # xb: (B_loc, S, D); w*: (E_loc, D/fsdp, F_loc).
        first = jax.lax.axis_index(ep_axis) * e_loc
        bl, sl, dl = xb.shape
        if fsdp_axes and decode:
            # no-weight-gather path: see _local_moe_sharded_weights
            out, aux = _local_moe_sharded_weights(
                cfg, xb.reshape(bl * sl, dl), router, wg, wu, wd,
                first, factor, fsdp_axes,
            )
        else:
            if fsdp_axes:  # train/prefill: gathers amortized over T tokens
                wg = jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, fsdp_axes, axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, fsdp_axes, axis=2, tiled=True)
            out, aux = _local_moe(
                cfg, xb.reshape(bl * sl, dl), router, wg, wu, wd, first, factor
            )
        out = jax.lax.psum(out.astype(xb.dtype), ep_axis)
        aux = jax.lax.pmean(aux, ep_axis)
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return out.reshape(bl, sl, dl), aux

    fsdp_spec = fsdp_axes[0] if len(fsdp_axes) == 1 else (fsdp_axes or None)
    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch_axes, None, None),
            P(None, None),
            P(ep_axis, fsdp_spec, None),
            P(ep_axis, fsdp_spec, None),
            P(ep_axis, None, fsdp_spec),
        ),
        out_specs=(P(batch_axes, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, aux


def moe_apply(
    cfg: ModelConfig, p: dict, x: Array, *, decode: bool = False
) -> tuple[Array, Array]:
    """Dispatch on ``cfg.moe_impl`` (gather-EP vs all-to-all EP)."""
    if cfg.moe_impl == "a2a" and moe_factor(cfg) == 1 and not decode:
        return moe_ffn_a2a(cfg, p, x)
    return moe_ffn(cfg, p, x, decode=decode)
