"""Public wrapper for the fold-in kernel.

Adapts the serving data model (pre-gathered phi rows, one PRNG key, traced
hyperparams) to the kernel's layout: the caller gathers the phi rows of
every request token **once** (C7 — the kernel then reuses them across all
sweeps), the per-sweep uniforms and initial assignments are drawn exactly
as the XLA path in ``repro.serve.infer`` draws them (same key splits, so
all three impls are draw-identical), and alpha/beta travel as a (2,) array
so a hot-swapped snapshot never recompiles.

Taking the gathered rows (not the full phi) is what makes the kernel
partition-agnostic: under V-sharded serving each device holds only its
local phi block, the per-token gather runs on the shard owning each word
id, and the psum'd (B, L, K) rows are all the kernel ever sees.

Called from inside ``repro.serve.infer``'s jits; not jitted itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.sampler import pstar

from . import kernel, ref


def init_assignments(key, batch: int, length: int, num_topics: int):
    """The fold-in's initial (B, L) int32 topic assignments from the init
    key — the single z0 draw routine shared by every serving path (XLA,
    Pallas, sharded), enforced by the ``prng-discipline`` checker."""
    return jax.random.randint(key, (batch, length), 0, num_topics,
                              jnp.int32)


def sweep_uniforms(key, batch: int, length: int):
    """One sweep's (B, L, 2) uniforms from its sweep key — the single
    serving-sweep draw routine (see ``init_assignments``).  Always drawn at
    FULL batch shape: counter-based PRNG values depend on the draw shape,
    so sharded consumers slice rows out of this rather than drawing a
    (Bs, L, 2) block."""
    return jax.random.uniform(key, (batch, length, 2), jnp.float32)


def draw_fold_in_randoms(key, batch: int, length: int, num_topics: int,
                         n_sweeps: int):
    """The fold-in's entire randomness budget, drawn up front.

    Same split tree as the XLA serving path (init key -> z0; one key per
    sweep -> a (B, L, 2) uniform block), so every consumer of these arrays
    is draw-identical to it.  Drawing at full batch shape and *slicing* is
    how the V-sharded all2all path keeps bit-identity while each shard
    sweeps only its doc slice (see ``sweep_uniforms``).

    Returns (z0 (B, L) int32, uniforms (n_sweeps, B, L, 2) float32)."""
    k_init, k_sweeps = jax.random.split(key)
    z0 = init_assignments(k_init, batch, length, num_topics)
    keys = jax.random.split(k_sweeps, n_sweeps)
    uniforms = jax.vmap(
        functools.partial(sweep_uniforms, batch=batch, length=length))(keys)
    return z0, uniforms


def fold_in_sweeps_drawn(
    phi_tok,       # (b, L, K) int32 — gathered phi rows (b may be a slice)
    phi_sum,       # (K,) int32
    mask,          # (b, L) bool
    z0,            # (b, L) int32 — pre-drawn initial assignments
    uniforms,      # (n_sweeps, b, L, 2) float32 — pre-drawn per-sweep draws
    alpha,         # traced scalars (hot-swap without recompiling)
    beta,
    *,
    num_words_total: int,
    burn_in: int,
    samples: int,
    ell_capacity: int,
    impl: str,
    interpret: bool,
):
    """The sweeps on pre-drawn randomness; returns per-doc partials over the
    kept sweeps: (theta_sum (b, K) int32, sparse_draws (b,) int32,
    ssq_sum (b,) float32).  ``impl`` is ``"xla"`` or ``"pallas"``."""
    uni = jnp.swapaxes(uniforms, 0, 1)                    # (b, n_sweeps, L, 2)
    kw = dict(burn_in=burn_in, samples=samples, ell_capacity=ell_capacity)
    if impl == "pallas":
        # C7: the per-token p* rows, by the XLA path's own division
        pstar_tok = pstar(phi_tok, phi_sum, beta, num_words_total)
        return kernel.fold_in_docs(
            pstar_tok, jnp.float32(alpha), uni[..., 0], uni[..., 1],
            mask.astype(jnp.int32), z0, interpret=interpret, **kw)
    if impl != "xla":
        raise ValueError(f"unknown fold-in impl {impl!r}: 'xla' | 'pallas'")
    hyper = jnp.stack([jnp.float32(alpha), jnp.float32(beta)])
    return ref.fold_in_docs_ref(
        phi_tok.astype(jnp.int32), phi_sum.astype(jnp.int32), hyper, uni,
        mask.astype(jnp.int32), z0, num_words_total=num_words_total, **kw)


def fold_in_sweeps(
    phi_tok,       # (B, L, K) int32 — gathered phi rows of the request tokens
    phi_sum,       # (K,) int32
    mask,          # (B, L) bool
    key,
    alpha,
    beta,
    *,
    num_words_total: int,
    burn_in: int,
    samples: int,
    ell_capacity: int,
    impl: str,
    interpret: bool,
):
    """Run all fold-in sweeps from a PRNG key; returns the per-doc partials
    of ``fold_in_sweeps_drawn``."""
    B, L = mask.shape
    z0, uniforms = draw_fold_in_randoms(key, B, L, phi_sum.shape[0],
                                        burn_in + samples)
    return fold_in_sweeps_drawn(
        phi_tok, phi_sum, mask, z0, uniforms, alpha, beta,
        num_words_total=num_words_total, burn_in=burn_in, samples=samples,
        ell_capacity=ell_capacity, impl=impl, interpret=interpret)
