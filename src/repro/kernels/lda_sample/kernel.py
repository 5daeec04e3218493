"""Pallas TPU kernel: the CuLDA_CGS sampler (paper §6.1), one word tile per
grid step.

GPU -> TPU mapping:
  * thread block sharing one word's p* in shared memory
        -> the tile's p* row (computed by XLA for every word) DMA'd from HBM
           into VMEM, its C5 search tables built once there and shared by
           every token of the tile;
  * per-token theta/ELL reads from global memory
        -> each real token's (1, P) ELL row DMA'd straight from HBM into a
           per-tile VMEM table (doc ids ride in SMEM), so no per-token
           ``(n, t, P)`` ELL tensor ever exists in HBM; padding slots of a
           tile issue no DMA at all;
  * 32 warp-samplers per block
        -> the tile's t tokens sampled in lock-step on the VPU;
  * 32-ary shared-memory index tree (C5)
        -> 128-lane two-level blocked search (``sampler.search_tables``);
  * short-int compression (C7)
        -> int16 z widened in-register by the wrapper.

Mosaic lowers no ``cumsum`` and no general gather, so the body uses the
shared ``sampler.prefix_sum`` (shifted adds via ``pltpu.roll``) and gathers
by exact selects: p*[topic] is a 128-lane ``take_along_axis`` per 128-topic
block, a per-token pick is a masked lane sum.  Every float op matches
``repro.core.sampler.sample_one_tile`` one for one, so draws are
bit-identical to the ``"sq"`` sweep wherever both run on the same backend.

Array layout: per-tile rows are ``(n, 1, t)`` and tables ``(rows, 1, width)``
so that every block or DMA takes whole trailing dims (the TPU's (8, 128)
tiling forbids one-row slices of a 2-D array).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sampler import prefix_sum
from repro.kernels.lanes import (VMEM_LIMIT_BYTES, dense_draw, gather_lanes,
                                  lane_pick, row_block, search_rows)

HEAD = 128  # SMEM header lanes before a tile's doc ids: [word, n_real, 0...]


def _kernel(
    head_ref,       # SMEM (1, HEAD + t) int32: [word, n_real, 0.., doc ids]
    pstar_hbm,      # ANY (V, 1, K) float32 — p*(k) of every word
    ell_cnt_hbm,    # ANY (D, 1, P) int32
    ell_tpc_hbm,    # ANY (D, 1, P) int32
    u1_ref,         # (1, t) float32 — branch uniform
    u2_ref,         # (1, t) float32 — search uniform
    mask_ref,       # (1, t) int32
    z_old_ref,      # (1, t) int32
    z_new_ref,      # out (1, t) int32
    sparse_ref,     # out (1, t) int32 — drew from p1?
    ssq_ref,        # out (1, t) float32 — per-token S/(S+Q), 0 on pads
    tok_cnt,        # VMEM (t, 1, P) int32 — each token's ELL counts
    tok_tpc,        # VMEM (t, 1, P) int32 — ... and topics
    pstar_scr,      # VMEM (1, K) float32 — the tile's p* row
    local_scr,      # VMEM (1, K) float32
    u1_col,         # VMEM (t, 1) float32 — u1 as a column
    u2_col,         # VMEM (t, 1) float32
    z_col,          # VMEM (t, 1) int32 — results as columns
    sp_col,         # VMEM (t, 1) int32
    ssq_col,        # VMEM (t, 1) float32
    sem,            # DMA semaphores (3,)
    *,
    alpha: float,
):
    t = z_old_ref.shape[1]
    P = tok_cnt.shape[2]
    R = row_block(t)

    # ---- stage the tile: its p* row and every real token's ELL row ----
    # Three phases carry a ``jax.named_scope``, which Mosaic lowers to a
    # device trace region (one per grid step): ``lda_sample.issue`` (the
    # DMA starts), ``lda_sample.wait`` (the drain) and ``lda_sample.rows``
    # (the row loop).  Regions touch no values, so draws are unchanged.
    n_real = head_ref[0, 1]

    def copies(j, doc):
        return (pltpu.make_async_copy(ell_cnt_hbm.at[doc], tok_cnt.at[j],
                                      sem.at[1]),
                pltpu.make_async_copy(ell_tpc_hbm.at[doc], tok_tpc.at[j],
                                      sem.at[2]))

    def issue(j, carry):
        for c in copies(j, head_ref[0, HEAD + j]):
            c.start()
        return carry

    def drain(j, carry):
        for c in copies(0, 0):   # waits count bytes: any same-shape copy
            c.wait()
        return carry

    with jax.named_scope("lda_sample.issue"):
        pstar_copy = pltpu.make_async_copy(pstar_hbm.at[head_ref[0, 0]],
                                           pstar_scr, sem.at[0])
        pstar_copy.start()
        jax.lax.fori_loop(0, n_real, issue, 0)
    with jax.named_scope("lda_sample.wait"):
        jax.lax.fori_loop(0, n_real, drain, 0)
        pstar_copy.wait()

    # C7: p* is computed by XLA for every word (``sampler.pstar``, the same
    # division the XLA sweep makes); C5 tables once per tile
    bcum, total, nb = search_rows(pstar_scr[...], local_scr)
    Q = alpha * total                                             # (1, 1)

    # C4 sparse side over each token's ELL row, R tokens at a time (one
    # fixed-size body keeps the kernel small).  Rows past n_real hold stale
    # data; everything is row-local and masked out at the end.
    u1_col[...] = jnp.transpose(u1_ref[...])                      # (t, 1)
    u2_col[...] = jnp.transpose(u2_ref[...])

    def rows(r, carry):
        r0 = pl.multiple_of(r * R, R)
        sl = pl.ds(r0, R)
        cnt = tok_cnt[sl].reshape(R, P).astype(jnp.float32)
        tpc = tok_tpc[sl].reshape(R, P)
        p1 = cnt * gather_lanes(pstar_scr, tpc)                   # (R, P)
        p1_cum = prefix_sum(p1, roll=pltpu.roll)
        S = lane_pick(p1_cum, jnp.full((R, 1), P - 1, jnp.int32))  # (R, 1)
        u1, u2 = u1_col[sl], u2_col[sl]
        use_sparse = u1 * (S + Q) < S
        # sparse draw: search the P-entry prefix sums
        jj = jnp.minimum(
            jnp.sum((p1_cum <= u2 * S).astype(jnp.int32), -1, keepdims=True),
            P - 1)
        k_sparse = lane_pick(tpc, jj)
        # dense draw: two-level blocked search (C5)
        k_dense = dense_draw(local_scr, bcum, nb, u2 * total)
        z_col[sl] = jnp.where(use_sparse, k_sparse, k_dense)
        sp_col[sl] = use_sparse.astype(jnp.int32)
        ssq_col[sl] = S / jnp.maximum(S + Q, 1e-30)
        return carry

    # only the row blocks holding real tokens (most tiles of the Zipf tail
    # hold a few); the columns of the others are stale and masked out below
    with jax.named_scope("lda_sample.rows"):
        jax.lax.fori_loop(0, (n_real + R - 1) // R, rows, 0)
    mask = mask_ref[...] != 0
    z_new_ref[...] = jnp.where(mask, jnp.transpose(z_col[...]),
                               z_old_ref[...])
    sparse_ref[...] = jnp.where(mask, jnp.transpose(sp_col[...]), 0)
    ssq_ref[...] = jnp.where(mask, jnp.transpose(ssq_col[...]), 0.0)


def grid_layout(n: int, t: int, K: int, P: int):
    """Launch geometry: ``(grid, in_specs, out_specs, scratch_shapes)``.

    Single source of truth — ``lda_sample_tiles`` launches from this and the
    ``kernel-contract`` checker (``contract.py``) enumerates it."""
    row = pl.BlockSpec((None, 1, t), lambda i: (i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((None, 1, HEAD + t), lambda i: (i, 0, 0),
                     memory_space=pltpu.SMEM),
        hbm,                                              # p* rows
        hbm, hbm,                                         # ELL counts/topics
        row, row, row, row,                               # u1, u2, mask, z
    ]
    out_specs = [row, row, row]
    scratch_shapes = [
        pltpu.VMEM((t, 1, P), jnp.int32),
        pltpu.VMEM((t, 1, P), jnp.int32),
        pltpu.VMEM((1, K), jnp.float32),
        pltpu.VMEM((1, K), jnp.float32),
        pltpu.VMEM((t, 1), jnp.float32),
        pltpu.VMEM((t, 1), jnp.float32),
        pltpu.VMEM((t, 1), jnp.int32),
        pltpu.VMEM((t, 1), jnp.int32),
        pltpu.VMEM((t, 1), jnp.float32),
        pltpu.SemaphoreType.DMA((3,)),
    ]
    return (n,), in_specs, out_specs, scratch_shapes


def tile_head(tile_word, token_doc, token_mask):
    """The per-tile SMEM header ``(n, 1, HEAD + t)``: word id, real-token
    count, then the doc id of every slot (real tokens are left-packed)."""
    n, t = token_doc.shape
    meta = jnp.zeros((n, HEAD), jnp.int32)
    meta = meta.at[:, 0].set(tile_word).at[:, 1].set(token_mask.sum(1))
    return jnp.concatenate([meta, token_doc], axis=1).reshape(n, 1, HEAD + t)


def lda_sample_tiles(
    tile_word,     # (n,) int32
    token_doc,     # (n, t) int32
    pstar_vk,      # (V, K) float32 — ``sampler.pstar`` of every word
    ell_counts,    # (D, P) int32 — per-DOC ELL, fetched per real token
    ell_topics,    # (D, P) int32
    u1,            # (n, t) float32
    u2,            # (n, t) float32
    token_mask,    # (n, t) int32
    z_old,         # (n, t) int32
    *,
    alpha: float,
    interpret: bool,
):
    """pallas_call wrapper: grid over word tiles.  Returns (z_new, sparse,
    ssq), all (n, t).

    The ELL width is padded to whole 128-lane vregs: the extra entries have
    count 0, add exact zeros to every prefix sum and are never drawn."""
    n, t = z_old.shape
    V, K = pstar_vk.shape
    pad = -ell_counts.shape[1] % 128
    ell_counts = jnp.pad(ell_counts, ((0, 0), (0, pad)))
    ell_topics = jnp.pad(ell_topics, ((0, 0), (0, pad)))
    D, P = ell_counts.shape
    grid, in_specs, out_specs, scratch_shapes = grid_layout(n, t, K, P)
    kern = functools.partial(_kernel, alpha=alpha)
    rows = lambda a: a.reshape(n, 1, t)  # noqa: E731
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
        out_shape=[
            jax.ShapeDtypeStruct((n, 1, t), jnp.int32),
            jax.ShapeDtypeStruct((n, 1, t), jnp.int32),
            jax.ShapeDtypeStruct((n, 1, t), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="lda_sample",
    )(tile_head(tile_word, token_doc, token_mask),
      pstar_vk.reshape(V, 1, K), ell_counts.reshape(D, 1, P), ell_topics.reshape(D, 1, P),
      rows(u1), rows(u2), rows(token_mask), rows(z_old))
    return tuple(o.reshape(n, t) for o in out)
