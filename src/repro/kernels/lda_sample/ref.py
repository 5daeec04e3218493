"""Pure-jnp oracle for the lda_sample kernel.

The kernel's contract, written the plain way: every tile through
``repro.core.sampler.sample_one_tile`` (the ``"sq"`` sweep's tile step),
with the per-token ELL rows gathered as ordinary XLA gathers.  The kernel
must match it draw for draw given the same uniforms.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sampler import sample_one_tile


def lda_sample_tiles_ref(
    tile_word,     # (n,) int32
    token_doc,     # (n, t) int32
    phi_vk,        # (V, K) int32
    phi_sum,       # (K,) int32
    ell_counts,    # (D, P) int32
    ell_topics,    # (D, P) int32
    u1,            # (n, t) float32
    u2,            # (n, t) float32
    token_mask,    # (n, t) int32
    z_old,         # (n, t) int32
    *,
    alpha, beta, num_words_total,
):
    """Returns (z_new, sparse, ssq), all (n, t) — the kernel's contract."""
    step = functools.partial(sample_one_tile, alpha=alpha, beta=beta,
                             num_words_total=num_words_total)
    z_new, sparse, ssq = jax.vmap(
        step, in_axes=(0, None, 0, 0, 0, None, None, 0))(
        phi_vk[tile_word], phi_sum, token_doc, token_mask != 0, z_old,
        ell_counts, ell_topics, jnp.stack([u1, u2], axis=-1))
    return z_new, sparse.astype(jnp.int32), ssq
