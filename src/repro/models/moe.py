"""Mixture-of-Experts FFN with sort-based token dispatch.

Designed for very large expert counts (kimi-k2: 384 experts, top-8) where
the classic GShard one-hot dispatch einsum — O(T·E·C) memory — is
infeasible at 1M tokens. Instead, (token, choice) pairs are sorted by
expert id, positions within each expert are computed from the sorted
order, and tokens are scattered into a capacity-bounded (E, C, D) buffer
(dropping overflow, standard capacity-factor semantics). Cost:
O(T·K log(T·K)) sort + O(T·K·D) gather/scatter + O(E·C·D) buffer; the
buffer is sharded E→'model' (expert parallelism) and C→'data' so the
scatter lowers to the expected all-to-all on a 2-D mesh.

The router is in f32 (softmax over experts is precision-sensitive), with
an optional auxiliary load-balancing loss (Switch-style).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.constraint import ambient_mesh
from repro.models.layers import GATED_ACTIVATIONS, activation_fn, dense_init


def init_moe(key, d_model: int, num_experts: int, expert_d_ff: int,
             activation: str, dtype, router_dtype=jnp.float32) -> dict:
    kr, k1, k2 = jax.random.split(key, 3)
    gated = activation in GATED_ACTIVATIONS
    scale_in = 1.0 / math.sqrt(d_model)
    scale_out = 1.0 / math.sqrt(expert_d_ff)
    if gated:
        # (E, D, 2, F): gate/up stacked on a separate axis so an F-shard
        # (the FSDP axis) always holds ALIGNED gate/up pairs — required by
        # the token-routed decode path, which computes with F-sharded
        # expert weights in place.
        wi = jax.random.normal(k1, (num_experts, d_model, 2, expert_d_ff))
    else:
        wi = jax.random.normal(k1, (num_experts, d_model, expert_d_ff))
    return {
        "router": dense_init(kr, d_model, num_experts, router_dtype),
        "wi": (wi * scale_in).astype(dtype),
        "wo": (jax.random.normal(k2, (num_experts, expert_d_ff, d_model)) * scale_out).astype(dtype),
    }


def expert_capacity(num_tokens: int, num_experts: int, top_k: int,
                    capacity_factor: float, multiple_of: int = 8) -> int:
    cap = math.ceil(num_tokens * top_k * capacity_factor / num_experts)
    return max(multiple_of, multiple_of * math.ceil(cap / multiple_of))


def moe_ffn(
    p: dict,
    x: jax.Array,
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    activation: str = "silu",
    shard_experts: Optional[str] = "model",
    shard_capacity: Optional[str] = "data",
    return_aux: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Routed expert FFN. x: (B, S, D) → (B, S, D), aux-loss scalar.

    Two execution paths:
      * **shard_map** (used whenever an ambient mesh with a "model" axis is
        present and divides the expert count): tokens are replicated across
        the model axis within each data column, so each model rank
        dispatches *locally* to the experts it owns — zero dispatch
        collectives — computes them with FSDP-gathered weights, and a
        single psum over "model" combines. This is the production path;
        letting GSPMD partition the global formulation instead replicates
        the (T·K, D) dispatch tensors on every device (240 GB for kimi-k2).
      * **global** (no mesh — CPU tests, single device): sort-based
        dispatch into a capacity-bounded (E, C, D) buffer.
    """
    mesh = ambient_mesh()
    e = p["router"].shape[-1]
    if mesh is not None and "model" in mesh.axis_names:
        model_n = mesh.shape["model"]
        dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp_n = 1
        for a in dp_axes:
            dp_n *= mesh.shape[a]
        t = x.shape[0] * x.shape[1]
        if e % model_n == 0 and t % max(dp_n, 1) == 0:
            return _moe_shard_map(
                p, x, mesh=mesh, dp_axes=dp_axes, top_k=top_k,
                capacity_factor=capacity_factor, activation=activation,
                return_aux=return_aux)
    return _moe_global(
        p, x, top_k=top_k, capacity_factor=capacity_factor,
        activation=activation, shard_experts=shard_experts,
        shard_capacity=shard_capacity, return_aux=return_aux)


def _local_dispatch_compute(xf, router, wi, wo, *, e_loc, e_lo, top_k, cap,
                            activation, return_aux, n_model):
    """Per-device MoE: local sort-based dispatch over the owned experts.

    xf: (T_loc, D) tokens of this data column (replicated over model);
    wi: (E_loc, D, Wio) / wo: (E_loc, F, D) — this model rank's experts.
    Returns (partial y (T_loc, D) — caller psums over "model", aux).
    """
    t_loc, d = xf.shape
    act = activation_fn(activation)

    logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)  # (T_loc, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # keep only choices routed to experts this model rank owns; the rest
    # go to an overflow bucket (e_loc) that is dropped.
    flat_e = expert_idx.reshape(t_loc * top_k).astype(jnp.int32)
    mine = (flat_e >= e_lo) & (flat_e < e_lo + e_loc)
    local_e = jnp.where(mine, flat_e - e_lo, e_loc)

    sort_order = jnp.argsort(local_e)
    sorted_e = local_e[sort_order]
    expert_start = jnp.searchsorted(sorted_e, jnp.arange(e_loc + 1, dtype=jnp.int32))
    pos_in_expert = jnp.arange(t_loc * top_k, dtype=jnp.int32) - expert_start[
        jnp.clip(sorted_e, 0, e_loc)]
    keep = (pos_in_expert < cap) & (sorted_e < e_loc)
    slot = jnp.where(keep, pos_in_expert, 0)
    token_of = sort_order // top_k

    gathered = xf[token_of] * keep[:, None].astype(xf.dtype)
    expert_in = jnp.zeros((e_loc + 1, cap, d), dtype=xf.dtype)
    expert_in = expert_in.at[jnp.clip(sorted_e, 0, e_loc), slot].add(
        gathered, mode="drop")[:e_loc]

    if wi.ndim == 4:  # gated: (E, D, 2, F) — works with full or sharded F
        h = jnp.einsum("ecd,edgf->ecgf", expert_in, wi)
        h = act(h[:, :, 0]) * h[:, :, 1]
    else:
        h = act(jnp.einsum("ecd,edf->ecf", expert_in, wi))
    expert_out = jnp.einsum("ecf,efd->ecd", h, wo)

    contrib = expert_out[jnp.clip(sorted_e, 0, e_loc - 1), slot]
    w = gate_vals.reshape(t_loc * top_k)[sort_order].astype(contrib.dtype)
    contrib = contrib * (w * keep.astype(contrib.dtype))[:, None]
    y = jnp.zeros((t_loc, d), dtype=contrib.dtype)
    y = y.at[token_of].add(contrib)

    if return_aux:
        e = router.shape[-1]
        me = jnp.mean(probs, axis=0)
        pe = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32),
                      axis=0)
        aux = e * jnp.sum(me * pe)
    else:
        aux = jnp.zeros((), jnp.float32)
    return y, aux


def _moe_shard_map(p, x, *, mesh, dp_axes, top_k, capacity_factor,
                   activation, return_aux):
    """Dispatch between the two shard_map execution plans by napkin math.

    * **weight-gather plan** (train/prefill, T large): each model rank
      FSDP-gathers its experts' weights over the DP axes, dispatches its
      own data column's tokens locally (tokens are model-replicated), one
      psum over "model" combines. Collective bytes ≈ expert_params/model_n
      per device per layer.
    * **token-route plan** (decode, T small): weights stay fully sharded
      (E→model, F→data); the (tiny) token batch is all-gathered over DP,
      every device computes its (expert, F-shard) contribution, one psum
      over (model ∪ dp) combines, each DP rank keeps its token slice.
      Collective bytes ≈ a few × T·D per device per layer — for kimi-k2
      decode_32k this replaces a 4.5 GB/layer weight gather with ~5 MB of
      token traffic (§Perf hillclimb).
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    t = b * s
    e = p["router"].shape[-1]
    model_n = mesh.shape["model"]
    dp_n = 1
    for a in dp_axes:
        dp_n *= mesh.shape[a]
    e_loc = e // model_n
    t_loc = t // max(dp_n, 1)
    xf = x.reshape(t, d)
    dp = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    dsize = jnp.dtype(x.dtype).itemsize

    weight_gather_bytes = (p["wi"].size + p["wo"].size) * dsize // max(model_n, 1)
    token_route_bytes = 4 * t * d * dsize
    use_token_route = (dp_n > 1 and token_route_bytes < weight_gather_bytes)

    data_ax = "data" if "data" in mesh.axis_names else None
    wi_spec = (P("model", None, None, data_ax) if p["wi"].ndim == 4
               else P("model", None, data_ax))
    wo_spec = P("model", data_ax, None)

    if use_token_route:
        cap = expert_capacity(t, e, top_k, capacity_factor)

        def local_fn(xf_loc, router, wi_loc, wo_loc):
            x_all = jax.lax.all_gather(xf_loc, dp_axes, axis=0, tiled=True)
            m_idx = jax.lax.axis_index("model") if model_n > 1 else 0
            y_partial, aux = _local_dispatch_compute(
                x_all, router, wi_loc, wo_loc, e_loc=e_loc,
                e_lo=m_idx * e_loc, top_k=top_k, cap=cap,
                activation=activation, return_aux=return_aux,
                n_model=model_n)
            axes = (("model",) if model_n > 1 else ()) + dp_axes
            y_all = jax.lax.psum(y_partial, axes)  # combine experts + F shards
            r = jnp.zeros((), jnp.int32)
            for a in dp_axes:
                r = r * mesh.shape[a] + jax.lax.axis_index(a)
            y = jax.lax.dynamic_slice_in_dim(y_all, r * t_loc, t_loc, axis=0)
            return y, aux
    else:
        cap = expert_capacity(t_loc, e, top_k, capacity_factor)

        def local_fn(xf_loc, router, wi_loc, wo_loc):
            # FSDP: resolve this layer's expert weights (gather over DP)
            if dp_axes:
                wi_full = jax.lax.all_gather(wi_loc, dp_axes,
                                             axis=wi_loc.ndim - 1, tiled=True)
                wo_full = jax.lax.all_gather(wo_loc, dp_axes, axis=1, tiled=True)
            else:
                wi_full, wo_full = wi_loc, wo_loc
            m_idx = jax.lax.axis_index("model") if model_n > 1 else 0
            y_partial, aux = _local_dispatch_compute(
                xf_loc, router, wi_full, wo_full, e_loc=e_loc,
                e_lo=m_idx * e_loc, top_k=top_k, cap=cap,
                activation=activation, return_aux=return_aux,
                n_model=model_n)
            y = jax.lax.psum(y_partial, "model") if model_n > 1 else y_partial
            if return_aux and dp_axes:
                aux = jax.lax.pmean(aux, dp_axes)  # replicated along DP too
            return y, aux

    in_specs = (P(dp, None), P(None, None), wi_spec, wo_spec)
    out_specs = (P(dp, None), P())
    y, aux = jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(xf, p["router"], p["wi"], p["wo"])
    return y.reshape(b, s, d).astype(x.dtype), aux


def _moe_global(
    p: dict,
    x: jax.Array,
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    activation: str = "silu",
    shard_experts: Optional[str] = "model",
    shard_capacity: Optional[str] = "data",
    return_aux: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Global (no-mesh) path: sort-based dispatch with capacity."""
    b, s, d = x.shape
    t = b * s
    e = p["router"].shape[-1]
    cap = expert_capacity(t, e, top_k, capacity_factor)
    xf = x.reshape(t, d)

    from repro.distributed.constraint import shard_activation

    # ---- router (f32) ----
    logits = (xf.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    logits = shard_activation(logits, ("pod", "data"), None)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)  # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- sort (token, choice) pairs by expert ----
    flat_e = expert_idx.reshape(t * top_k).astype(jnp.int32)
    sort_order = jnp.argsort(flat_e)  # stable
    sorted_e = flat_e[sort_order]
    # first slot index of each expert in the sorted order
    expert_start = jnp.searchsorted(sorted_e, jnp.arange(e, dtype=jnp.int32))
    pos_in_expert = jnp.arange(t * top_k, dtype=jnp.int32) - expert_start[sorted_e]
    keep = pos_in_expert < cap
    slot = jnp.where(keep, pos_in_expert, 0)
    token_of = sort_order // top_k  # original token index per sorted pair

    # ---- dispatch: scatter tokens into the (E, C, D) buffer ----
    gathered = xf[token_of] * keep[:, None].astype(xf.dtype)
    # (T·K, D) rows in expert-sorted order: keep them sharded over the DP
    # axes — unconstrained, GSPMD replicates this tensor (T·K·D bytes on
    # every device; 240 GB for kimi-k2 at 1M tokens).
    gathered = shard_activation(gathered, ("pod", "data"), None)
    expert_in = jnp.zeros((e, cap, d), dtype=x.dtype)
    expert_in = expert_in.at[sorted_e, slot].add(gathered, mode="drop")
    expert_in = _shard(expert_in, (shard_experts, shard_capacity, None))

    # ---- expert computation ----
    act = activation_fn(activation)
    wi = p["wi"]
    if wi.ndim == 4:  # gated storage (E, D, 2, F) → fused (E, D, 2F)
        wi = wi.reshape(wi.shape[0], wi.shape[1], -1)
    h = jnp.einsum("ecd,edf->ecf", expert_in, wi)
    if activation in GATED_ACTIVATIONS:
        g, u = jnp.split(h, 2, axis=-1)
        h = act(g) * u
    else:
        h = act(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    expert_out = _shard(expert_out, (shard_experts, shard_capacity, None))

    # ---- combine: gather back and weight by (renormalized) gates ----
    contrib = expert_out[sorted_e, slot]  # (T·K, D)
    contrib = shard_activation(contrib, ("pod", "data"), None)
    w = gate_vals.reshape(t * top_k)[sort_order].astype(contrib.dtype)
    contrib = contrib * (w * keep.astype(contrib.dtype))[:, None]
    y = jnp.zeros((t, d), dtype=contrib.dtype)
    y = y.at[token_of].add(contrib)
    y = shard_activation(y, ("pod", "data"), None)

    # ---- Switch-style load-balance auxiliary loss ----
    if return_aux:
        me = jnp.mean(probs, axis=0)  # mean router prob per expert
        pe = jnp.mean(
            jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32), axis=0
        )  # fraction of tokens whose top-1 is e
        aux = e * jnp.sum(me * pe)
    else:
        aux = jnp.zeros((), jnp.float32)
    return y.reshape(b, s, d).astype(x.dtype), aux


def _shard(x: jax.Array, spec_axes: tuple) -> jax.Array:
    """Best-effort sharding constraint: apply only axes the ambient mesh has."""
    from jax.sharding import PartitionSpec as P

    mesh = ambient_mesh()
    if mesh is None:
        return x
    axes = tuple(a if (a in mesh.axis_names) else None for a in spec_axes)
    if all(a is None for a in axes):
        return x
    # avoid over-sharding tiny dims
    fixed = []
    for dim, a in zip(x.shape, axes):
        if a is not None and dim % mesh.shape[a] == 0:
            fixed.append(a)
        else:
            fixed.append(None)
    return jax.lax.with_sharding_constraint(x, P(*fixed))
