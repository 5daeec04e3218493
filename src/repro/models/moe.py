"""Mixture-of-Experts FFN (qwen3-moe family): top-k routing, expert parallel.

GShard-style dense dispatch: tokens are routed to experts via one-hot
dispatch/combine einsums with a fixed per-expert capacity.  This is the
TPU-idiomatic formulation — the scatter/gather of a ragged dispatch becomes
two MXU matmuls, experts shard cleanly over the "model" axis (EP=16 on the
production mesh), and the FLOP count reflects only routed tokens (times the
capacity-padding factor, reported in the roofline's MODEL_FLOPS/HLO ratio).

Routing: softmax over experts, top-k, renormalized combine weights
(qwen3-moe's norm_topk_prob=True convention).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .common import Array, ModelConfig, ShardingPolicy, init_dense


class MoEParams(NamedTuple):
    router: Array      # (D, E)
    w_gate: Array      # (E, D, F)
    w_up: Array        # (E, D, F)
    w_down: Array      # (E, F, D)


def init_moe(key, cfg: ModelConfig) -> MoEParams:
    ks = jax.random.split(key, 4)
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    return MoEParams(
        router=init_dense(ks[0], (D, E), D ** -0.5, jnp.float32),
        w_gate=init_dense(ks[1], (E, D, F), D ** -0.5, cfg.dtype),
        w_up=init_dense(ks[2], (E, D, F), D ** -0.5, cfg.dtype),
        w_down=init_dense(ks[3], (E, F, D), F ** -0.5, cfg.dtype),
    )


def moe_ffn(p: MoEParams, cfg: ModelConfig, x: Array,
            policy: ShardingPolicy) -> Array:
    """Dispatch to the EP path on a mesh, local dense dispatch otherwise."""
    if policy.enabled and policy.tp is not None and policy.mesh is not None:
        return moe_ffn_ep(p, cfg, x, policy)
    return moe_ffn_local(p, cfg, x, policy)


def moe_ffn_local(p: MoEParams, cfg: ModelConfig, x: Array,
                  policy: ShardingPolicy) -> Array:
    """x: (B, S, D) -> (B, S, D).  Capacity = ceil(T*k/E * cf)."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    T = B * S
    C = max(1, int(T * K / E * cfg.capacity_factor))
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ p.router)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)         # (T, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # rank of each (token, k) inside its expert's capacity buffer:
    # cumulative count of prior routings to the same expert
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)        # (T, K, E)
    flat_oh = onehot.reshape(T * K, E)
    rank = ((jnp.cumsum(flat_oh, axis=0) - flat_oh) * flat_oh).sum(-1)  # (T*K,)
    keep = rank < C                                               # capacity drop
    flat_e = gate_idx.reshape(T * K)
    slot = jnp.where(keep, rank, 0)

    # dispatch: scatter tokens into per-expert buffers (E, C, D)
    src = jnp.broadcast_to(xt[:, None, :], (T, K, D)).reshape(T * K, D)
    src = jnp.where(keep[:, None], src, 0)
    xe = jnp.zeros((E, C, D), x.dtype).at[flat_e, slot].add(src)
    xe = policy.constraint(xe, jax.sharding.PartitionSpec(policy.tp, None, None))

    h = jnp.einsum("ecd,edf->ecf", xe, p.w_gate.astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, p.w_up.astype(x.dtype))
    h = jax.nn.silu(h) * u
    ye = jnp.einsum("ecf,efd->ecd", h, p.w_down.astype(x.dtype))  # (E, C, D)

    # combine: gather each routing's output, weight, sum over k
    yk = ye[flat_e, slot]                                         # (T*K, D)
    yk = yk * (keep[:, None] * gate_vals.reshape(T * K)[:, None]).astype(x.dtype)
    y = yk.reshape(T, K, D).sum(1)
    return y.reshape(B, S, D)


def moe_ffn_ep(p: MoEParams, cfg: ModelConfig, x: Array,
               policy: ShardingPolicy) -> Array:
    """Expert parallelism over the TP axis (GShard/DeepSpeed-MoE pattern).

    shard_map region: every device dispatches its local tokens into E
    per-expert buckets (capacity C_loc), an **all-to-all over the model axis**
    regroups buckets so each device holds its E/|tp| experts' tokens from all
    peers, expert MLPs run on local weights (all-gathered over the FSDP axes),
    and the reverse all-to-all returns outputs for local combine.  Backward
    of all_to_all is all_to_all, of all_gather is reduce-scatter — i.e. the
    ZeRO gradient flow comes out of the transpose for free.
    """
    from jax.sharding import PartitionSpec as P

    dp = policy.batch()
    tp = policy.tp
    fs = policy._fs()
    mesh = policy.mesh
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    tp_size = int(dict(zip(mesh.axis_names, mesh.devices.shape))[tp])
    assert E % tp_size == 0, (E, tp_size)
    # decode steps have S=1: sequence can't shard over tp then
    seq = tp if (x.shape[1] % tp_size == 0 and x.shape[1] > 1) else None

    def local_moe(xl, router, wg, wu, wd):
        # xl: (B_loc, S_loc, D); expert weights sharded over dp on dim 1/2
        if fs:
            wg = jax.lax.all_gather(wg, fs, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fs, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fs, axis=2, tiled=True)
        Bl, Sl, D = xl.shape
        T = Bl * Sl
        C = max(1, -(-T * K // E))  # ceil; capacity factor via padding below
        C = max(1, int(C * cfg.capacity_factor))
        xt = xl.reshape(T, D)
        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
        oh = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32).reshape(T * K, E)
        rank = ((jnp.cumsum(oh, axis=0) - oh) * oh).sum(-1)
        keep = rank < C
        flat_e = gate_idx.reshape(T * K)
        slot = jnp.where(keep, rank, 0)
        src = jnp.broadcast_to(xt[:, None, :], (T, K, D)).reshape(T * K, D)
        src = jnp.where(keep[:, None], src, 0)
        xe = jnp.zeros((E, C, D), xl.dtype).at[flat_e, slot].add(src)
        # all-to-all: (E, C, D) -> (E/tp, C*tp, D)
        xe = jax.lax.all_to_all(xe, tp, split_axis=0, concat_axis=1, tiled=True)
        h = jnp.einsum("ecd,edf->ecf", xe, wg.astype(xl.dtype))
        u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(xl.dtype))
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd.astype(xl.dtype))
        ye = jax.lax.all_to_all(ye, tp, split_axis=1, concat_axis=0, tiled=True)
        yk = ye[flat_e, slot]
        yk = yk * (keep[:, None] * gate_vals.reshape(T * K)[:, None]).astype(xl.dtype)
        return yk.reshape(T, K, D).sum(1).reshape(Bl, Sl, D)

    fn = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(dp, seq, None), P(None, None),
                  P(tp, fs, None), P(tp, fs, None), P(tp, None, fs)),
        out_specs=P(dp, seq, None), check_vma=False)
    x = policy.constraint(x, P(dp, seq, None))
    return fn(x, p.router, p.w_gate, p.w_up, p.w_down)
