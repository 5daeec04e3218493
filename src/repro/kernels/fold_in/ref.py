"""Pure-jnp fold-in sweeps: the kernel's oracle and the XLA serving path.

Batched mirror of ``kernel.py`` with the same decomposed contract (explicit
z0 + per-sweep uniforms in, per-doc theta-sum / sparse / S-share partials
out).  Uses ``jax.lax.top_k`` for the ELL slice — the kernel's iterative
selection must match it bit-for-bit, tie order included — and the shared
``repro.core.sampler`` prefix sums and blocked search, so the kernel and
this code draw identically given the same uniforms.  ``repro.serve.infer``
runs this as its ``"xla"`` implementation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import sampler, updates


def fold_in_docs_ref(
    phi_tok,       # (B, L, K) int32 — pre-gathered phi rows
    phi_sum,       # (K,) int32
    hyper,         # (2,) float32 — [alpha, beta]
    uniforms,      # (B, n_sweeps, L, 2) float32
    mask,          # (B, L) int32
    z0,            # (B, L) int32
    *,
    num_words_total: int,
    burn_in: int,
    samples: int,
    ell_capacity: int,
):
    nB, L, K = phi_tok.shape
    P = ell_capacity
    alpha, beta = hyper[0], hyper[1]
    maskb = mask != 0                                     # (B, L)

    pstar = sampler.pstar(phi_tok, phi_sum, beta, num_words_total)  # (B,L,K)
    local, bcum, total = sampler.search_tables(pstar)     # per-token C5 tree
    Q = alpha * total                                     # (B, L)
    draw = jax.vmap(sampler.blocked_draw)
    local_f = local.reshape(nB * L, K)
    bcum_f = bcum.reshape(nB * L, bcum.shape[-1])

    # the training count-rebuild primitive with one "doc" per batch row
    rows = jnp.broadcast_to(jnp.arange(nB, dtype=jnp.int32)[:, None], (nB, L))

    def theta_counts(z):
        return updates.theta_from_z(z, rows, maskb, nB, K)

    def sweep(carry, u):
        z, theta = carry  # delayed counts: whole sweep vs sweep-start theta
        cnt, tpc = jax.lax.top_k(theta, P)                # (B, P) ELL slice
        gat = jnp.broadcast_to(tpc[:, None, :], (nB, L, P))
        p1 = cnt[:, None, :].astype(jnp.float32) * jnp.take_along_axis(
            pstar, gat, axis=-1)                          # (B, L, P)
        p1_cum = sampler.prefix_sum(p1)
        S = p1_cum[..., -1]                               # (B, L)

        u1, u2 = u[..., 0], u[..., 1]
        use_sparse = u1 * (S + Q) < S

        j = jnp.minimum(jnp.sum((p1_cum <= (u2 * S)[..., None]).astype(
            jnp.int32), -1), P - 1)
        k_sparse = jnp.take_along_axis(tpc, j, axis=1)
        k_dense = draw(local_f, bcum_f, (u2 * total).reshape(-1)).reshape(
            nB, L)

        z_new = jnp.where(use_sparse, k_sparse, k_dense).astype(jnp.int32)
        z_new = jnp.where(maskb, z_new, z)
        theta_new = theta_counts(z_new)
        sp = (use_sparse & maskb).astype(jnp.int32).sum(-1)          # (B,)
        ssq = jnp.where(maskb, S / jnp.maximum(S + Q, 1e-30), 0.0).sum(-1)
        return (z_new, theta_new), (theta_new, sp, ssq)

    uni = jnp.swapaxes(uniforms, 0, 1)                    # (n_sweeps, B, L, 2)
    carry = (z0, theta_counts(z0))
    carry, _ = jax.lax.scan(sweep, carry, uni[:burn_in])
    _, (thetas, sps, ssqs) = jax.lax.scan(sweep, carry, uni[burn_in:])
    return thetas.sum(0), sps.sum(0), ssqs.sum(0)
