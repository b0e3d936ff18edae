"""Mixture-of-Experts layer: top-k routing, capacity-bounded sort/scatter
dispatch, expert parallelism via shard_map all-to-all, switch-style aux loss.

Two execution paths share the same parameters and routing math:

* ``moe_apply_local``  — single-device (or data-parallel-replicated-experts)
  grouped compute.  Used in CPU smoke tests and as the oracle for the EP path.
* ``moe_apply_ep``     — expert parallelism: tokens are sequence-sharded over
  the TP mesh axis, redistributed to the devices owning their experts with an
  ``all_to_all``, processed by the local expert group, and sent back.  This is
  the deployment path inside the jitted step (shard_map region).

Token overflow beyond ``capacity_factor`` is dropped (contributes only the
residual/shared-expert path), matching switch/dbrx semantics.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import ParamDef, _act, _gated

Params = Any


def moe_schema(cfg) -> Dict[str, ParamDef]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    wi_cols = 2 * f if _gated(cfg.mlp_activation) else f
    # Expert weights shard over the EP axis only ("experts" -> model); the
    # within-expert dims use "expert_inner" (-> None) so one PartitionSpec
    # never maps two dims to the same mesh axis.
    sch = {
        "router": ParamDef((d, e), ("embed", "experts_r"), scale=0.1),
        "wi": ParamDef((e, d, wi_cols), ("experts", "embed", "expert_inner")),
        "wo": ParamDef((e, f, d), ("experts", "expert_inner", "embed")),
    }
    if cfg.moe.shared_expert:
        sch["shared_wi"] = ParamDef((d, wi_cols), ("embed", "ffn"))
        sch["shared_wo"] = ParamDef((f, d), ("ffn", "embed"))
    return sch


def _route(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (T, d) -> (topk_gate (T,k) fp32, topk_idx (T,k) int32, gates (T,E))."""
    logits = (x.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_g, top_i = jax.lax.top_k(gates, cfg.moe.top_k)
    top_g = top_g / jnp.maximum(jnp.sum(top_g, -1, keepdims=True), 1e-9)
    return top_g, top_i.astype(jnp.int32), gates


def _aux_stats(gates: jax.Array, top_i: jax.Array, num_experts: int):
    """(density, frac) for the switch load-balance loss; kept separate so
    the EP path can pmean each BEFORE the product (exact global loss)."""
    density = jnp.mean(gates, axis=0)  # (E,)
    onehot = jax.nn.one_hot(top_i[:, 0], num_experts, dtype=jnp.float32)
    frac = jnp.mean(onehot, axis=0)
    return density, frac


def _aux_loss(gates: jax.Array, top_i: jax.Array, num_experts: int) -> jax.Array:
    """Switch-transformer load-balance loss."""
    density, frac = _aux_stats(gates, top_i, num_experts)
    return num_experts * jnp.sum(density * frac)


def _dispatch(
    x: jax.Array, top_g: jax.Array, top_i: jax.Array, num_experts: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort-free scatter dispatch.  x:(T,d) -> buffer (E, C, d).

    Returns (buffer, slot (T,k), keep (T,k) fp32, flat order info for combine).
    """
    T, k = top_i.shape
    # position of (t, j) within its expert = count of same-expert assignments
    # with smaller flat index; computed via cumsum over one-hot.
    flat_e = top_i.reshape(-1)  # (T*k,)
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)  # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot  # exclusive cumsum
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]  # (T*k,)
    keep = (slot < capacity).astype(x.dtype)
    slot = jnp.minimum(slot, capacity - 1)
    tok = jnp.repeat(jnp.arange(T), k)
    buf = jnp.zeros((num_experts, capacity, x.shape[-1]), x.dtype)
    buf = buf.at[flat_e, slot].add(x[tok] * keep[:, None])
    return buf, slot.reshape(T, k), keep.reshape(T, k), tok


def _expert_ffn(wi: jax.Array, wo: jax.Array, buf: jax.Array, cfg) -> jax.Array:
    """buf: (E, C, d) -> (E, C, d) through each expert's MLP."""
    h = jnp.einsum("ecd,edf->ecf", buf, wi.astype(buf.dtype))
    if _gated(cfg.mlp_activation):
        gate, up = jnp.split(h, 2, axis=-1)
        h = _act(cfg.mlp_activation, gate) * up
    else:
        h = _act(cfg.mlp_activation, h)
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(buf.dtype))


def _combine(
    buf_out: jax.Array, top_g: jax.Array, top_i: jax.Array,
    slot: jax.Array, keep: jax.Array, T: int,
) -> jax.Array:
    """Gather expert outputs back to token order, weighted by gates."""
    k = top_i.shape[1]
    flat_e = top_i.reshape(-1)
    flat_s = slot.reshape(-1)
    picked = buf_out[flat_e, flat_s]  # (T*k, d)
    w = (top_g * keep.astype(top_g.dtype)).reshape(-1, 1).astype(picked.dtype)
    picked = picked * w
    return jnp.sum(picked.reshape(T, k, -1), axis=1)


def _capacity(tokens: int, cfg) -> int:
    c = int(tokens * cfg.moe.top_k * cfg.moe.capacity_factor / cfg.moe.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8, floor at 8


def moe_apply_local(p: Params, x: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """(B, S, d) -> (B, S, d), aux loss.  No expert parallelism."""
    B, S, d = x.shape
    xt = x.reshape(B * S, d)
    top_g, top_i, gates = _route(p, xt, cfg)
    aux = _aux_loss(gates, top_i, cfg.moe.num_experts)
    C = _capacity(B * S, cfg)
    buf, slot, keep, _ = _dispatch(xt, top_g, top_i, cfg.moe.num_experts, C)
    buf = _expert_ffn(p["wi"], p["wo"], buf, cfg)
    out = _combine(buf, top_g, top_i, slot, keep, B * S)
    if cfg.moe.shared_expert:
        h = xt @ p["shared_wi"].astype(xt.dtype)
        g, u = jnp.split(h, 2, axis=-1)
        out = out + (_act(cfg.mlp_activation, g) * u) @ p["shared_wo"].astype(xt.dtype)
    return out.reshape(B, S, d), aux


def moe_apply_ep(
    p: Params, x: jax.Array, cfg, mesh, *,
    dp_axes: Tuple[str, ...], tp_axis: str,
) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE: shard_map region inside the jitted step.

    x is (B, S, d) global; inside the region each device sees its
    (B/dp, S/tp, d) block.  Experts are sharded over ``tp_axis``.
    """
    E = cfg.moe.num_experts
    tp = mesh.shape[tp_axis]
    assert E % tp == 0, f"experts {E} must divide over tp={tp}"
    e_local = E // tp

    def local_fn(xl, router, wi_l, wo_l, *shared):
        # xl: (Bl, Sl, d); wi_l: (e_local, d, F2); experts sharded over tp.
        Bl, Sl, d = xl.shape
        T = Bl * Sl
        xt = xl.reshape(T, d)
        pr = {"router": router}
        top_g, top_i, gates = _route(pr, xt, cfg)
        density, frac = _aux_stats(gates, top_i, E)
        axes_all = (tp_axis,) + tuple(dp_axes)
        density = jax.lax.pmean(density, axes_all)
        frac = jax.lax.pmean(frac, axes_all)
        aux = E * jnp.sum(density * frac)  # exact global load-balance loss
        C = _capacity(T, cfg)
        buf, slot, keep, _ = _dispatch(xt, top_g, top_i, E, C)  # (E, C, d)
        # redistribute: split E across tp peers, exchange
        buf = buf.reshape(tp, e_local, C, d)
        buf = jax.lax.all_to_all(buf, tp_axis, 0, 0, tiled=False)  # (tp, e_local, C, d)
        buf = buf.transpose(1, 0, 2, 3).reshape(e_local, tp * C, d)
        out = _expert_ffn(wi_l, wo_l, buf, cfg)  # (e_local, tp*C, d)
        out = out.reshape(e_local, tp, C, d).transpose(1, 0, 2, 3)  # (tp, e_local, C, d)
        out = jax.lax.all_to_all(out, tp_axis, 0, 0, tiled=False)
        out = out.reshape(E, C, d)
        y = _combine(out, top_g, top_i, slot, keep, T)
        if shared:
            swi, swo = shared
            h = xt @ swi.astype(xt.dtype)
            g, u = jnp.split(h, 2, axis=-1)
            y = y + (_act(cfg.mlp_activation, g) * u) @ swo.astype(xt.dtype)
        return y.reshape(Bl, Sl, d), aux

    B_, S_, _ = x.shape
    ndp = 1
    for a in dp_axes:
        ndp *= mesh.shape[a]
    batch_axes = dp_axes if len(dp_axes) != 1 else dp_axes[0]
    batch_ok = dp_axes and B_ % max(ndp, 1) == 0 and B_ >= ndp
    seq_ok = S_ % tp == 0 and S_ >= tp  # decode: S=1 stays unsharded
    x_spec = P(batch_axes if batch_ok else None, tp_axis if seq_ok else None, None)
    shared_args = ()
    shared_specs = ()
    if cfg.moe.shared_expert:
        shared_args = (p["shared_wi"], p["shared_wo"])
        shared_specs = (P(None, None), P(None, None))
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), P(tp_axis, None, None), P(tp_axis, None, None))
        + shared_specs,
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(x, p["router"], p["wi"], p["wo"], *shared_args)


def moe_apply(
    p: Params, x: jax.Array, cfg, runtime=None
) -> Tuple[jax.Array, jax.Array]:
    """Dispatcher: EP path when a mesh runtime is provided, local otherwise."""
    if runtime is not None and runtime.mesh is not None and runtime.ep_enabled(cfg):
        return moe_apply_ep(
            p, x, cfg, runtime.mesh,
            dp_axes=runtime.dp_axes, tp_axis=runtime.tp_axis,
        )
    return moe_apply_local(p, x, cfg)
