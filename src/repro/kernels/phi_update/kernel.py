"""Pallas TPU kernel: phi count update as one-hot MXU matmuls (paper §6.2).

The paper updates phi with atomic adds exploiting word-locality (tokens are
word-sorted so consecutive atomics hit the same row).  TPU has no atomics;
the same locality becomes a **VMEM accumulator**: the grid walks tiles in
word order, each tile's counts add into a (1, K) VMEM row, and because tiles
of one word are adjacent the row is DMA'd to its word's HBM row once, after
the word's last tile.  The per-tile count vector itself is computed as a
ones x one-hot matmul — a (1, t) @ (t, K) systolic pass — which is the
TPU-idiomatic segmented reduction.

``tile_first`` (host-precomputed, = paper's word boundaries) zeroes the
accumulator on a word's first tile; the last-tile flag derived from it
triggers the write.  Padding tiles alias the last real word with
tile_first=False and a zero mask, so they are exact no-ops.  Word ids and
flags ride in per-tile SMEM blocks, not in scalar prefetch: SMEM holds 1 MiB,
too little for a per-tile table at corpus scale.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _counts(z_row, m_row, num_topics: int):
    """(1, t) topics and mask -> (1, K) f32 counts: ones x one-hot on the MXU
    (exact: 0/1 operands, at most t per lane)."""
    z = jnp.transpose(z_row)                                  # (t, 1)
    m = jnp.transpose(m_row).astype(jnp.float32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, num_topics), 1)
    return (z == iota).astype(jnp.float32) * m                # (t, K)


def _accumulate(meta_ref, out_hbm, acc, sem, counts):
    """Add a tile's counts to its word's row; write the row out after the
    word's last tile (tiles of one word are adjacent)."""
    @pl.when(meta_ref[0, 1] == 1)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += counts.astype(jnp.int32)

    @pl.when(meta_ref[0, 2] == 1)
    def _flush():
        copy = pltpu.make_async_copy(acc, out_hbm.at[meta_ref[0, 0]], sem)
        copy.start()
        copy.wait()


def _kernel(meta_ref, z_ref, mask_ref, out_hbm, acc, sem, *,
            num_topics: int):
    onehot = _counts(z_ref[...], mask_ref[...], num_topics)
    ones = jnp.ones((1, onehot.shape[0]), jnp.float32)
    _accumulate(meta_ref, out_hbm, acc, sem,
                jnp.dot(ones, onehot, preferred_element_type=jnp.float32))


def _delta_kernel(meta_ref, z_new_ref, z_old_ref, mask_ref, out_hbm, acc,
                  sem, *, num_topics: int):
    """Incremental variant: counts(z_new) - counts(z_old) per tile, both
    one-hot MXU passes fused into one grid step (the word's row accumulates
    across its tiles exactly like the full rebuild)."""
    diff = (_counts(z_new_ref[...], mask_ref[...], num_topics)
            - _counts(z_old_ref[...], mask_ref[...], num_topics))
    ones = jnp.ones((1, diff.shape[0]), jnp.float32)
    _accumulate(meta_ref, out_hbm, acc, sem,
                jnp.dot(ones, diff, preferred_element_type=jnp.float32))


META = 128  # SMEM lanes per tile: [word, first, last, 0...]


def grid_layout(n: int, t: int, num_topics: int, *, delta: bool):
    """Launch geometry: ``(grid, in_specs, out_spec, scratch_shapes)``.

    Single source of truth — both wrappers launch from this and the
    ``kernel-contract`` checker (``contract.py``) enumerates it.  Tile rows
    are ``(n, 1, t)`` so every block takes whole trailing dims; each tile's
    word / first / last flags ride in an SMEM block; the (V, 1, K) output
    stays in HBM and is written one finished word row at a time.  The
    delta variant carries one extra input (z_old)."""
    n_rows = 3 if delta else 2
    in_specs = [pl.BlockSpec((None, 1, META), lambda i: (i, 0, 0),
                             memory_space=pltpu.SMEM)]
    in_specs += [pl.BlockSpec((None, 1, t), lambda i: (i, 0, 0))
                 for _ in range(n_rows)]
    out_spec = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [pltpu.VMEM((1, num_topics), jnp.int32),
               pltpu.SemaphoreType.DMA(())]
    return (n,), in_specs, out_spec, scratch


def tile_meta(tile_word, tile_first):
    """(n, 1, META) int32: word id, first-tile flag, last-tile flag."""
    n = tile_word.shape[0]
    first = tile_first.astype(jnp.int32)
    last = jnp.concatenate([first[1:], jnp.ones((1,), jnp.int32)])
    meta = jnp.zeros((n, META), jnp.int32)
    meta = meta.at[:, 0].set(tile_word).at[:, 1].set(first).at[:, 2].set(last)
    return meta.reshape(n, 1, META)


def _launch(kern, tile_word, tile_first, rows, num_words, num_topics,
            delta, interpret):
    n, t = rows[0].shape
    grid, in_specs, out_spec, scratch = grid_layout(n, t, num_topics,
                                                    delta=delta)
    out = pl.pallas_call(
        functools.partial(kern, num_topics=num_topics),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=scratch,
        out_shape=jax.ShapeDtypeStruct((num_words, 1, num_topics), jnp.int32),
        interpret=interpret,
        name="phi_delta" if delta else "phi_update",
    )(tile_meta(tile_word.astype(jnp.int32), tile_first),
      *(r.reshape(n, 1, t) for r in rows))
    return out.reshape(num_words, num_topics)


def phi_delta_tiles(
    tile_word,    # (n,) int32
    tile_first,   # (n,) int32 (1 on the first tile of each word run)
    z_new,        # (n, t) int32
    z_old,        # (n, t) int32
    token_mask,   # (n, t) int32
    num_words: int,
    num_topics: int,
    *,
    interpret: bool,
):
    """Accumulate the per-iteration phi DELTA (V, K) int32 from word tiles."""
    return _launch(_delta_kernel, tile_word, tile_first,
                   (z_new, z_old, token_mask), num_words, num_topics,
                   True, interpret)


def phi_update_tiles(
    tile_word,    # (n,) int32
    tile_first,   # (n,) int32 (1 on the first tile of each word run)
    z,            # (n, t) int32
    token_mask,   # (n, t) int32
    num_words: int,
    num_topics: int,
    *,
    interpret: bool,
):
    """Accumulate phi counts (V, K) int32 from word tiles."""
    return _launch(_kernel, tile_word, tile_first, (z, token_mask),
                   num_words, num_topics, False, interpret)
