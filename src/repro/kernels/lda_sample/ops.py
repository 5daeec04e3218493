"""Public wrapper for the lda_sample kernel.

Adapts the trainer's data model (per-doc ELL, int16 z, bool masks) to the
kernel's layout and exposes an ``impl={"pallas","ref"}`` switch.

The wrapper performs **no per-token HBM gather**: the kernel wrapper packs
the per-doc ELL counts and topics into one (D, P) table of int32 words, and
the kernel DMAs each real token's packed row straight into VMEM, so the
``(n, t, P)`` tensor ``ell_counts[token_doc]`` exists nowhere
(``tests/test_kernels.py`` pins this by jaxpr shape accounting).

Randomness contract: uniforms come from ``sampler.draw_sweep_uniforms`` —
the same (n, t, 2) tensor the XLA sweep consumes — so kernel draws are
bit-identical to ``sampler.sample_sweep`` under the same key.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sampler import SamplerStats, draw_sweep_uniforms, pstar

from . import kernel, ref


@functools.partial(jax.jit, static_argnames=(
    "alpha", "beta", "num_words_total", "impl", "interpret"))
def lda_sample(
    tile_word, token_doc, token_mask, z, phi_vk, phi_sum,
    ell_counts, ell_topics, key, *,
    alpha: float, beta: float, num_words_total: int,
    impl: str, interpret: bool,
):
    """Sample one sweep of word tiles.

    Returns ``(z_new, SamplerStats)`` with z_new like ``z`` and draws
    bit-identical to ``sampler.sample_sweep`` under the same key.
    """
    n, t = z.shape
    # same uniforms as the XLA sweep: one key per tile
    uniforms = draw_sweep_uniforms(key, n, t)
    tw, td = tile_word.astype(jnp.int32), token_doc.astype(jnp.int32)
    rest = (ell_counts.astype(jnp.int32), ell_topics.astype(jnp.int32),
            uniforms[..., 0], uniforms[..., 1],
            token_mask.astype(jnp.int32), z.astype(jnp.int32))
    if impl == "pallas":
        # p* of every word, by the same XLA division the sq sweep makes
        pstar_vk = pstar(phi_vk, phi_sum, beta, num_words_total)
        z_new, sparse, ssq, widths = kernel.lda_sample_tiles(
            tw, td, pstar_vk, *rest, alpha=alpha, interpret=interpret)
        width_share = kernel.row_width_share(widths, ell_counts.shape[1])
    else:
        z_new, sparse, ssq = ref.lda_sample_tiles_ref(
            tw, td, phi_vk.astype(jnp.int32), phi_sum.astype(jnp.int32),
            *rest, alpha=alpha, beta=beta, num_words_total=num_words_total)
        width_share = jnp.float32(1)
    total = jnp.maximum(token_mask.sum(), 1)
    stats = SamplerStats(sparse_frac=sparse.sum() / total,
                         mean_s_over_sq=ssq.sum() / total,
                         row_width_share=width_share)
    return z_new.astype(z.dtype), stats
