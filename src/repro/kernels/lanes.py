"""Mosaic-lowerable building blocks shared by the Pallas kernels.

Mosaic (the TPU kernel compiler) lowers no ``cumsum`` and gathers only
inside one 128-lane vreg, so the sampling kernels are written with these
pieces: exact lane picks and 128-lane gathers (selects, no arithmetic) and
the C5 search tables built from ``repro.core.sampler.prefix_sum``.  Every
float op matches the XLA samplers' one for one.  Per-token values are
(rows, 1) columns; tables are (rows, width) VMEM refs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core.sampler import pick_search_block, prefix_sum

# Mosaic's scoped-VMEM limit for the kernels (v5e has 128 MiB of VMEM; the
# 16 MiB default is too tight for the (t, 1, P) ELL tables plus temporaries)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

_roll = pltpu.roll


def row_block(t: int) -> int:
    """Tokens sampled per loop step: 32 (a few vregs per (rows, P) array),
    or the whole tile when it is shorter or not a multiple."""
    return 32 if t % 32 == 0 else t


def lane_pick(x, idx):
    """x[r, idx[r]] for (R, W) ``x`` and (R, 1) ``idx`` as a masked lane sum
    (exact: one term is non-zero).  Out-of-range ``idx`` gives 0."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == idx, x, jnp.zeros_like(x)), axis=-1,
                   keepdims=True)


def gather_lanes(table_ref, idx):
    """``table[r, idx[r, p]]`` for a (R or 1, K) VMEM ref and (R, P) int32
    indices in [0, K), exactly (a gather has no arithmetic).

    Mosaic gathers only inside one 128-lane vreg, so for K a multiple of 128
    the table is cut into 128-topic blocks: each block answers the indices
    whose high bits name it.  Other widths (interpret mode only) take one
    block of width K.
    """
    K = table_ref.shape[-1]
    R, P = idx.shape
    G = 128 if K % 128 == 0 else K
    W = 128 if P % 128 == 0 else P
    if G & (G - 1) == 0:   # the TPU case: shifts, not integer division
        hi, lo = idx >> (G.bit_length() - 1), idx & (G - 1)
    else:
        hi, lo = idx // G, idx % G
    out = jnp.zeros((R, P), table_ref.dtype)
    for b in range(K // G):
        blk = jnp.broadcast_to(table_ref[:, b * G:(b + 1) * G], (R, G))
        parts = [jnp.take_along_axis(blk, lo[:, g * W:(g + 1) * W], axis=1,
                                     mode="promise_in_bounds")
                 for g in range(P // W)]
        got = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        out = jnp.where(hi == b, got, out)
    return out


def search_lanes(K: int) -> int:
    """Lane width of the block-prefix row ``search_rows`` returns: the nb
    block sums padded to whole 128-lane vregs."""
    nb = K // pick_search_block(K)
    return max(128, -(-nb // 128) * 128)


def search_rows(pstar, local_scr):
    """``sampler.search_tables`` for (R, K) rows inside a kernel.

    Stores the block-local prefix sums in ``local_scr`` (read back per block
    by ``dense_draw``) and returns ``(bcum (R, W), total (R, 1), nb)`` with
    the nb block prefix sums in the first lanes of a W >= 128 lane row."""
    R, K = pstar.shape
    B = pick_search_block(K)
    nb = K // B
    local = prefix_sum(pstar, block=B, roll=_roll)
    local_scr[...] = local
    W = search_lanes(K)
    lane_w = jax.lax.broadcasted_iota(jnp.int32, (R, W), 1)
    bsum = jnp.zeros((R, W), jnp.float32)
    for b in range(nb):
        v = lane_pick(local, jnp.full((R, 1), b * B + B - 1, jnp.int32))
        bsum = jnp.where(lane_w == b, v, bsum)
    bcum = prefix_sum(bsum, roll=_roll)   # lanes < nb == prefix_sum(nb)
    total = lane_pick(bcum, jnp.full((R, 1), nb - 1, jnp.int32))
    return bcum, total, nb


def dense_draw(local_scr, bcum, nb: int, target):
    """``sampler.blocked_draw`` for (R, 1) targets, each searched in row r
    (or the single row) of the tables built by ``search_rows``."""
    K = local_scr.shape[-1]
    B = K // nb
    R = target.shape[0]
    lane_w = jax.lax.broadcasted_iota(jnp.int32, bcum.shape, 1)
    below = (bcum <= target) & (lane_w < nb)
    b_idx = jnp.minimum(jnp.sum(below.astype(jnp.int32), -1, keepdims=True),
                        nb - 1)                                   # (R, 1)
    prev = lane_pick(jnp.broadcast_to(bcum, (R, bcum.shape[1])), b_idx - 1)
    seg = jnp.zeros((R, B), jnp.float32)
    for b in range(nb):
        seg = jnp.where(b_idx == b, local_scr[:, b * B:(b + 1) * B], seg)
    seg_cum = seg + prev
    in_b = jnp.minimum(
        jnp.sum((seg_cum <= target).astype(jnp.int32), -1, keepdims=True),
        B - 1)
    return b_idx * B + in_b


