"""jit'd public wrapper for the phi_update kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel, ref


@functools.partial(jax.jit, static_argnames=("num_words", "num_topics",
                                             "impl", "interpret"))
def phi_update(tile_word, tile_first, z, token_mask, *,
               num_words: int, num_topics: int,
               impl: str, interpret: bool):
    args = (tile_word.astype(jnp.int32), tile_first.astype(jnp.int32),
            z.astype(jnp.int32), token_mask.astype(jnp.int32))
    if impl == "pallas":
        out = kernel.phi_update_tiles(*args, num_words, num_topics,
                                      interpret=interpret)
        # output blocks of words with no tiles are never visited and hold
        # undefined memory — zero them (same contract on real TPU)
        visited = jnp.zeros((num_words,), jnp.int32).at[args[0]].set(1)
        return jnp.where(visited[:, None] == 1, out, 0)
    return ref.phi_update_tiles_ref(*args, num_words, num_topics)


@functools.partial(jax.jit, static_argnames=("num_words", "num_topics",
                                             "impl", "interpret"))
def phi_delta(tile_word, tile_first, z_old, z_new, token_mask, *,
              num_words: int, num_topics: int,
              impl: str, interpret: bool):
    """Per-iteration phi DELTA (V, K) int32: counts(z_new) - counts(z_old).

    The trainer adds this to the previous phi instead of rebuilding counts
    from scratch — one pass over the tokens (the ``compressed_sync`` branch
    used to pay two full rebuilds just to form this difference).
    """
    args = (tile_word.astype(jnp.int32), tile_first.astype(jnp.int32),
            z_new.astype(jnp.int32), z_old.astype(jnp.int32),
            token_mask.astype(jnp.int32))
    if impl == "pallas":
        out = kernel.phi_delta_tiles(*args, num_words, num_topics,
                                     interpret=interpret)
        visited = jnp.zeros((num_words,), jnp.int32).at[args[0]].set(1)
        return jnp.where(visited[:, None] == 1, out, 0)
    return ref.phi_delta_tiles_ref(*args, num_words, num_topics)
