"""Mixture-of-Experts block — expert parallelism via shard_map.

Design (DESIGN.md §4):
  * expert weights sharded [experts -> 'model', embed -> 'data' (FSDP)]
  * activations enter dp-sharded and TP-replicated (the baseline layout),
    so each model-rank routes the *full local* token block, packs only the
    tokens destined for its local experts (sort-free: cumsum positions),
    runs the expert FFN, and a psum over 'model' combines expert outputs
    AND restores TP replication — no explicit all-to-all needed.
  * the FSDP all-gather of expert weights over 'data' is explicit
    (jax.lax.all_gather inside the shard_map), mirroring what XLA's
    sharded-weight gather does for the dense layers.

Capacity-factor token dropping (standard top-k capacity MoE) with an
auxiliary load-balancing loss.  The single-device path (ctx disabled) runs
the identical packing math with E_local = E and no collectives, so smoke
tests exercise the same numerics the production mesh runs.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import cast
from repro.models.schema import Leaf
from repro.models.sharding import ShardingCtx


def moe_schema(cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = {
        "router": Leaf((d, e), ("embed_act", "experts"), init="normal"),
        "wi": Leaf((e, d, f), ("experts", "embed", "expert_mlp"), fan_axis=1),
        "wo": Leaf((e, f, d), ("experts", "expert_mlp", "embed"), fan_axis=1),
    }
    if cfg.mlp_gated:
        s["wg"] = Leaf((e, d, f), ("experts", "embed", "expert_mlp"),
                       fan_axis=1)
    return s


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(tokens * cfg.experts_per_token * cfg.moe_capacity_factor
            / cfg.num_experts)
    return max(8, c)


def _moe_local(xt, router_w, wi, wg, wo, cfg: ModelConfig,
               e_local: int, rank, capacity: int):
    """Per-device MoE compute.

    xt: [T, d] local tokens (replicated across TP ranks);
    wi/wg/wo: this rank's expert slab [E_local, d, f] / [E_local, f, d];
    rank: TP rank (experts [rank*E_local, (rank+1)*E_local) are local).
    Returns (out [T, d] — only local experts' contribution, aux metrics).
    """
    t, d = xt.shape
    e, k = cfg.num_experts, cfg.experts_per_token

    # router in fp32: top-k tie stability across shardings/reduction orders
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                  # [T, k]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # aux load-balancing loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)                            # [E]
    ce = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / (t * k))
    aux = e * jnp.sum(me * ce)

    flat_e = top_e.reshape(-1)                              # [T*k]
    flat_w = top_w.reshape(-1)
    e0 = rank * e_local
    local_id = flat_e - e0                                  # [T*k]
    is_local = (local_id >= 0) & (local_id < e_local)

    # position within each local expert via cumsum of one-hot [T*k, E_local]
    onehot = jax.nn.one_hot(jnp.where(is_local, local_id, e_local),
                            e_local + 1, dtype=jnp.int32)[:, :e_local]
    pos = jnp.cumsum(onehot, axis=0) - onehot               # exclusive
    pos_in_e = jnp.sum(pos * onehot, axis=1)                # [T*k]
    keep = is_local & (pos_in_e < capacity)
    dest = jnp.where(keep, local_id * capacity + pos_in_e, e_local * capacity)

    tok = jnp.arange(t * k) // k
    gathered = jnp.take(xt, tok, axis=0)                    # [T*k, d]
    xe = jnp.zeros((e_local * capacity + 1, d), xt.dtype).at[dest].add(
        jnp.where(keep[:, None], gathered, 0))
    xe = xe[:-1].reshape(e_local, capacity, d)

    h = jnp.einsum("ecd,edf->ecf", xe, cast(wi))
    if wg is not None:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, cast(wg))) * h
    else:
        h = jax.nn.gelu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, cast(wo))

    ye_flat = jnp.concatenate(
        [ye.reshape(e_local * capacity, d),
         jnp.zeros((1, d), ye.dtype)], axis=0)
    contrib = jnp.take(ye_flat, dest, axis=0)               # [T*k, d]
    contrib = contrib * (flat_w * keep).astype(contrib.dtype)[:, None]
    out = jnp.zeros((t, d), xt.dtype).at[tok].add(contrib)

    dropped = 1.0 - jnp.sum(keep.astype(jnp.float32)) / jnp.maximum(
        jnp.sum(is_local.astype(jnp.float32)), 1.0)
    return out, aux, dropped


def moe_block(params, x, cfg: ModelConfig, ctx: ShardingCtx):
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar)."""
    b, s, d = x.shape
    wg = params.get("wg")

    if not ctx.enabled or ctx.tp_size() == 1:
        xt = x.reshape(b * s, d)
        cap = _capacity(b * s, cfg)
        out, aux, _ = _moe_local(xt, params["router"], params["wi"], wg,
                                 params["wo"], cfg, cfg.num_experts, 0, cap)
        return out.reshape(b, s, d), aux

    mesh = ctx.mesh
    tp = ctx.tp_axis
    fsdp = ctx.fsdp_axis
    e_local = cfg.num_experts // ctx.tp_size()
    dp_spec = tuple(ctx.dp_axes) if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]

    t_local = (b // ctx.dp_size()) * s
    cap = _capacity(t_local, cfg)

    x_spec = P(dp_spec, None, None)
    gated = wg is not None
    all_axes = tuple(ctx.dp_axes) + (tp,)

    def _sharded(xb, router_w, wi, wo, *rest):
        # FSDP gather of this rank's expert slab over 'data'
        wi_full = jax.lax.all_gather(wi, fsdp, axis=1, tiled=True)
        wo_full = jax.lax.all_gather(wo, fsdp, axis=2, tiled=True)
        wg_full = (jax.lax.all_gather(rest[0], fsdp, axis=1, tiled=True)
                   if gated else None)
        rank = jax.lax.axis_index(tp)
        xt = xb.reshape(-1, d)
        out, aux, dropped = _moe_local(xt, router_w, wi_full, wg_full,
                                       wo_full, cfg, e_local, rank, cap)
        # combine expert contributions across TP ranks; aux averaged over
        # the whole mesh so the out_spec can declare it replicated
        out = jax.lax.psum(out, tp)
        aux = jax.lax.pmean(aux, all_axes)
        return out.reshape(xb.shape), aux

    in_specs = [x_spec, P(None, None), P(tp, fsdp, None), P(tp, None, fsdp)]
    args = [x, params["router"], params["wi"], params["wo"]]
    if gated:
        in_specs.append(P(tp, fsdp, None))
        args.append(wg)
    fn = jax.shard_map(
        _sharded, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(x_spec, P()), check_vma=False)
    out, aux = fn(*args)
    return out, aux
