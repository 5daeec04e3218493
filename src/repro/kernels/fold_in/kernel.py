"""Pallas TPU kernel: frozen-phi fold-in sweeps (serving hot path).

One grid step = one request document.  The XLA fold-in path
(``ref.py``) re-materializes the O(B*L*K) per-token p* product and the
(B, L, P) sparse side from HBM on *every* sweep; here the whole sweep loop
runs on-chip per doc:

  * the (L, K) p* rows of the request's tokens (C7: gathered and divided
    once per request by the wrapper in ``ops.py``, with the XLA path's own
    ``sampler.pstar``) and their C5 search tables sit in VMEM, reused by
    every burn-in + sample sweep;
  * the doc's (1, K) theta counts live in VMEM across sweeps — the
    delayed-count carry never round-trips to HBM;
  * the C4 S/Q split and the two-level blocked search run with the
    Mosaic-lowerable pieces of ``repro.kernels.lanes``: shifted-add prefix
    sums, 128-lane gathers and masked lane picks.

The ELL slice of theta (the XLA path's ``jax.lax.top_k``) is an iterative
max selection loop — bit-identical to ``lax.top_k`` including tie order
(largest value first, ties broken toward the lower topic id).

alpha enters as a (1, 1) array, not as a static closure constant, so a
hot-swapped snapshot with different hyperparams never recompiles — the same
contract as the XLA path, where alpha and beta are traced scalars.

Tokens are processed ``row_block(L)`` at a time so the kernel body stays
small; per-token values live in (L, 1) VMEM columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sampler import pick_search_block, prefix_sum
from repro.kernels.lanes import (VMEM_LIMIT_BYTES, dense_draw, gather_lanes,
                                  lane_pick, row_block, search_lanes,
                                  search_rows)

_INT_MIN = jnp.iinfo(jnp.int32).min


def ell_lanes(ell_capacity: int, K: int) -> int:
    """Lane width of the kernel's ELL slice: ``ell_capacity`` rounded up to
    whole 128-lane vregs, at most K."""
    return min(-(-ell_capacity // 128) * 128, K)


def _ell_topk(theta, P: int, cap: int):
    """(1, K) counts -> (1, P) descending (counts, topics), == ``lax.top_k``
    of width ``cap`` in lanes ``< cap``; lanes ``>= cap`` hold count 0.

    Selection loop: cap rounds of (max, first index holding it, mask-out),
    which reproduces top_k's tie order."""
    K = theta.shape[1]
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    p_iota = jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)

    def select(j, carry):
        w, cnt, tpc = carry
        v = jnp.max(w, axis=-1, keepdims=True)
        i = jnp.min(jnp.where(w == v, k_iota, K), axis=-1, keepdims=True)
        cnt = jnp.where(p_iota == j, v, cnt)
        tpc = jnp.where(p_iota == j, i, tpc)
        w = jnp.where(k_iota == i, _INT_MIN, w)
        return w, cnt, tpc

    zero = jnp.zeros((1, P), jnp.int32)
    _, cnt, tpc = jax.lax.fori_loop(0, cap, select, (theta, zero, zero))
    return cnt, tpc


def _kernel(
    pstar_ref,       # (L, K) float32 — this doc's per-token p* rows
    alpha_ref,       # (1, 1) float32 — traced (no recompile on hot-swap)
    u1_ref,          # (n_sweeps, 1, L) float32 — branch uniforms
    u2_ref,          # (n_sweeps, 1, L) float32 — search uniforms
    mask_ref,        # (1, L) int32
    z0_ref,          # (1, L) int32
    theta_sum_ref,   # out (1, K) int32 — sum of theta over the sample sweeps
    sp_ref,          # out (1, 1) int32 — sparse-side draws (sample sweeps)
    ssq_ref,         # out (1, 1) float32 — sum of S/(S+Q) over real tokens
    local_scr,       # VMEM (L, K) float32 — block-local prefix sums
    bcum_scr,        # VMEM (L, W) float32 — block prefix sums per token
    total_col,       # VMEM (L, 1) float32 — p* total per token
    u1_col,          # VMEM (L, 1) float32
    u2_col,          # VMEM (L, 1) float32
    z_col,           # VMEM (L, 1) int32 — current assignments
    mask_col,        # VMEM (L, 1) int32
    tpc_scr,         # VMEM (R, P) int32 — the sweep's ELL topics, per row
    *,
    burn_in: int,
    samples: int,
    ell_capacity: int,
):
    L, K = pstar_ref.shape
    P = tpc_scr.shape[1]                  # ell_lanes(ell_capacity, K)
    cap = ell_capacity
    R = row_block(L)
    n_blocks = L // R
    nb = K // pick_search_block(K)
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)
    p_iota = jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)

    alpha = alpha_ref[...]                                        # (1, 1)

    z_col[...] = jnp.transpose(z0_ref[...])
    mask_col[...] = jnp.transpose(mask_ref[...])

    # C5 tables of the per-token p* rows, once for all sweeps
    def tables(r, carry):
        sl = pl.ds(pl.multiple_of(r * R, R), R)
        bcum, total, _ = search_rows(pstar_ref[sl], local_scr.at[sl])
        bcum_scr[sl] = bcum
        total_col[sl] = total
        return carry

    jax.lax.fori_loop(0, n_blocks, tables, 0)

    def theta_counts():
        def body(r, acc):
            sl = pl.ds(pl.multiple_of(r * R, R), R)
            hits = (z_col[sl] == k_iota) & (mask_col[sl] != 0)     # (R, K)
            return acc + jnp.sum(hits.astype(jnp.int32), axis=0,
                                 keepdims=True)
        return jax.lax.fori_loop(0, n_blocks, body,
                                 jnp.zeros((1, K), jnp.int32))

    def sweep(s, carry):
        theta, tsum, sp, ssq = carry
        cnt, tpc = _ell_topk(theta, P, cap)               # (1, P) ELL slice
        cnt_f = cnt.astype(jnp.float32)
        tpc_scr[...] = jnp.broadcast_to(tpc, (R, P))
        u1_col[...] = jnp.transpose(u1_ref[s])
        u2_col[...] = jnp.transpose(u2_ref[s])

        def rows(r, acc):
            sp_r, ssq_r = acc
            sl = pl.ds(pl.multiple_of(r * R, R), R)
            tpc_r = tpc_scr[...]
            # C4 sparse side: p1 over the doc's <=P live topics
            p1 = cnt_f * gather_lanes(pstar_ref.at[sl], tpc_r)   # (R, P)
            # lane i < cap of the prefix sum sees lanes <= i only, so the
            # zero lanes >= cap change neither S nor the sparse search
            p1_cum = prefix_sum(p1, roll=pltpu.roll)
            S = lane_pick(p1_cum, jnp.full((R, 1), cap - 1, jnp.int32))
            total = total_col[sl]
            Q = alpha * total
            u1, u2 = u1_col[sl], u2_col[sl]
            use_sparse = u1 * (S + Q) < S
            # sparse draw: search the P-entry prefix sums
            below = (p1_cum <= u2 * S) & (p_iota < cap)
            j = jnp.minimum(jnp.sum(below.astype(jnp.int32), -1,
                                    keepdims=True), cap - 1)
            k_sparse = lane_pick(tpc_r, j)
            # dense draw: two-level blocked search (C5)
            k_dense = dense_draw(local_scr.at[sl], bcum_scr[sl], nb,
                                 u2 * total)
            m = mask_col[sl] != 0
            z_col[sl] = jnp.where(m, jnp.where(use_sparse, k_sparse, k_dense),
                                  z_col[sl])
            sp_r = sp_r + jnp.sum((use_sparse & m).astype(jnp.int32),
                                  keepdims=True)
            ssq_r = ssq_r + jnp.sum(
                jnp.where(m, S / jnp.maximum(S + Q, 1e-30), 0.0),
                keepdims=True)
            return sp_r, ssq_r

        sp_s, ssq_s = jax.lax.fori_loop(
            0, n_blocks, rows,
            (jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.float32)))
        theta_new = theta_counts()
        keep = s >= burn_in
        tsum = tsum + jnp.where(keep, theta_new, 0)
        sp = sp + jnp.where(keep, sp_s, 0)
        ssq = ssq + jnp.where(keep, ssq_s, 0.0)
        return theta_new, tsum, sp, ssq

    init = (theta_counts(), jnp.zeros((1, K), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), jnp.zeros((1, 1), jnp.float32))
    _, tsum, sp, ssq = jax.lax.fori_loop(0, burn_in + samples, sweep, init)
    theta_sum_ref[...] = tsum
    sp_ref[...] = sp
    ssq_ref[...] = ssq


def grid_layout(nB: int, L: int, K: int, n_sweeps: int, ell_capacity: int):
    """Launch geometry: ``(grid, in_specs, out_specs, scratch_shapes)``.

    Single source of truth — ``fold_in_docs`` launches from this and the
    ``kernel-contract`` checker (``contract.py``) enumerates it."""
    per_doc = lambda *blk: pl.BlockSpec(  # noqa: E731
        (None, *blk), lambda i: (i,) + (0,) * len(blk))
    in_specs = [
        per_doc(L, K),
        pl.BlockSpec((1, 1), lambda i: (0, 0)),
        per_doc(n_sweeps, 1, L),
        per_doc(n_sweeps, 1, L),
        per_doc(1, L),
        per_doc(1, L),
    ]
    out_specs = [per_doc(1, K), per_doc(1, 1), per_doc(1, 1)]
    scratch_shapes = [
        pltpu.VMEM((L, K), jnp.float32),
        pltpu.VMEM((L, search_lanes(K)), jnp.float32),
        pltpu.VMEM((L, 1), jnp.float32),
        pltpu.VMEM((L, 1), jnp.float32),
        pltpu.VMEM((L, 1), jnp.float32),
        pltpu.VMEM((L, 1), jnp.int32),
        pltpu.VMEM((L, 1), jnp.int32),
        pltpu.VMEM((row_block(L), ell_lanes(ell_capacity, K)), jnp.int32),
    ]
    return (nB,), in_specs, out_specs, scratch_shapes


def fold_in_docs(
    pstar_tok,     # (B, L, K) float32 — per-token p* rows (C7)
    alpha,         # () float32
    u1,            # (B, n_sweeps, L) float32
    u2,            # (B, n_sweeps, L) float32
    mask,          # (B, L) int32
    z0,            # (B, L) int32
    *,
    burn_in: int,
    samples: int,
    ell_capacity: int,
    interpret: bool,
):
    """pallas_call wrapper: grid over request docs, all sweeps fused on-chip.

    Returns (theta_sum (B, K) int32, sparse_draws (B,) int32,
    ssq_sum (B,) float32) — per-doc partials over the ``samples`` kept
    sweeps; ``ops.py`` folds them into the ``FoldInResult`` contract.
    """
    nB, L, K = pstar_tok.shape
    n_sweeps = burn_in + samples
    kern = functools.partial(_kernel, burn_in=burn_in, samples=samples,
                             ell_capacity=ell_capacity)
    grid, in_specs, out_specs, scratch = grid_layout(nB, L, K, n_sweeps,
                                                ell_capacity)
    theta_sum, sp, ssq = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
        out_shape=[
            jax.ShapeDtypeStruct((nB, 1, K), jnp.int32),
            jax.ShapeDtypeStruct((nB, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((nB, 1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fold_in",
    )(pstar_tok, jnp.reshape(alpha, (1, 1)),
      u1.reshape(nB, n_sweeps, 1, L), u2.reshape(nB, n_sweeps, 1, L),
      mask.reshape(nB, 1, L), z0.reshape(nB, 1, L))
    return theta_sum[:, 0], sp[:, 0, 0], ssq[:, 0, 0]
