"""Sparsity-aware S/Q sampler with blocked two-level search (paper §6.1).

Reproduces CuLDA_CGS's sampler design on the TPU programming model:

* C7 sub-expression reuse: per word tile, p*(k) = (phi_kv + b)/(phi_sum_k + bV)
  is computed once and reused by every token of the word (the paper kept it in
  shared memory; here it is a VMEM-resident (K,) vector per tile).
* C4 sparsity-aware split: p(k) = p1(k) + p2(k) with
  p1 = theta_dk * p*(k) (sparse over the <=P non-zero topics of doc d, ELL) and
  p2 = a * p*(k) (dense, word-shared).  S = sum p1 is O(K_d); Q = a * sum p*
  is computed once per tile, not per token.
* C5 tree search -> **two-level blocked search**: the K-long p* is reduced to
  nb = K/B block sums (level 1, the "index tree"), a draw first searches the
  nb cumulative block sums, then the B entries of the winning block.  B = 128
  follows the TPU lane width exactly as the paper's 32-ary tree followed the
  warp width.
* C6 parallelization: one tile = one word's tokens (the paper's thread block);
  the whole sweep is a scan over tile-chunks with a vmap inside (tens of
  thousands of concurrent "samplers").

Everything here is partition-agnostic: word ids in the tiles are *local* to
whatever phi shard the caller holds, so the same code serves the single
device, the paper-faithful 1D (phi replicated) and the 2D doc x word modes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray

SEARCH_BLOCK = 128  # level-1 tree arity == TPU lane width


class SamplerStats(NamedTuple):
    """Per-sweep diagnostics (cheap; all reduced scalars)."""

    sparse_frac: Array  # fraction of tokens drawn from p1 (sparsity hit rate)
    mean_s_over_sq: Array  # mean over tokens of S/(S+Q) — sparse mass share
    row_width_share: Array  # mean over row blocks of the ELL lanes sampled
    #                         over the padded width; 1 where all are


def pstar(phi_col: Array, phi_sum: Array, beta: float, num_words_total: int) -> Array:
    """C7: p*(k) for one word; phi_col (K,) int, phi_sum (K,) int.

    Public: shared by the training sweep and the fold-in inference path
    (repro.serve.infer), which evaluates the same Eq. 1 word factor against a
    frozen phi snapshot.
    """
    return (phi_col.astype(jnp.float32) + beta) / (
        phi_sum.astype(jnp.float32) + beta * num_words_total
    )


def pick_search_block(K: int) -> int:
    """Level-1 block width of the two-level search: the TPU lane width when
    it divides K, else the largest power of two that does.  Single source of
    the policy — the fold-in kernel/oracle must pick the same width or their
    draws diverge from this path.
    """
    return SEARCH_BLOCK if K % SEARCH_BLOCK == 0 else _pick_block(K)


def prefix_sum(x: Array, block: int | None = None, roll=jnp.roll, *,
               stop: int | None = None) -> Array:
    """Inclusive prefix sum along the last axis, as log2(n) shifted adds.

    The Hillis-Steele form: step ``d`` adds the element ``d`` lanes back.
    Only elementwise float adds, so the association is fixed by the
    algorithm and not by the backend — XLA on CPU or TPU, the Pallas
    kernels under Mosaic (``roll=pltpu.roll``) and in interpret mode all
    produce bit-identical sums.  This is the ONE prefix sum of every
    sampling path; ``jnp.cumsum`` is lowered differently per backend.

    ``block`` restarts the sum every ``block`` lanes (a segmented scan over
    contiguous blocks of the last axis).

    ``stop`` (a power of two) runs only the steps ``d < stop``: each lane
    then holds the sum of its ``stop``-lane window (``lda_sample`` runs the
    later steps over whole 128-lane chunks).
    """
    width = x.shape[-1] if block is None else block
    if stop is not None:
        width = min(width, stop)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    if block is not None:
        lane = lane % block
    d = 1
    while d < width:
        x = x + jnp.where(lane >= d, roll(x, d, x.ndim - 1), 0.0)
        d *= 2
    return x


def search_tables(pstar: Array) -> tuple[Array, Array, Array]:
    """C5 level-1 "index tree" of a weight vector (last axis K).

    Returns ``(local, bcum, total)``: the prefix sums inside each B-lane
    block (..., K), the prefix sums of the block totals (..., nb), and the
    grand total (...,) — the total is also what the S/Q split's Q uses.
    Everything is built from ``prefix_sum``, so the Pallas kernels rebuild
    the same tables bit for bit.
    """
    K = pstar.shape[-1]
    B = pick_search_block(K)
    nb = K // B
    local = prefix_sum(pstar, block=B)
    bsum = local.reshape(*pstar.shape[:-1], nb, B)[..., B - 1]
    bcum = prefix_sum(bsum)
    return local, bcum, bcum[..., nb - 1]


def blocked_draw(local: Array, bcum: Array, target: Array) -> Array:
    """C5: two-level search of ``target`` (...,) in one weight vector's
    ``search_tables`` (``local`` (K,), ``bcum`` (nb,)).  Returns int32
    topics like ``target``."""
    K, nb = local.shape[-1], bcum.shape[-1]
    B = K // nb
    t = target[..., None]
    b_idx = jnp.minimum(jnp.sum((bcum <= t).astype(jnp.int32), -1), nb - 1)
    prev = jnp.where(b_idx > 0, bcum[jnp.maximum(b_idx - 1, 0)], 0.0)
    seg_cum = local.reshape(nb, B)[b_idx] + prev[..., None]
    in_b = jnp.minimum(jnp.sum((seg_cum <= t).astype(jnp.int32), -1), B - 1)
    return (b_idx * B + in_b).astype(jnp.int32)


def _pick_block(K: int) -> int:
    for b in (128, 64, 32, 16, 8, 4, 2, 1):
        if K % b == 0:
            return b
    return 1


# Back-compat aliases (pre-serve these were module-private).
_pstar = pstar


def tile_uniforms(key: Array, t: int) -> Array:
    """One tile's (t, 2) sweep uniforms from its tile key.

    The ONLY training-sweep draw routine: every path (the XLA scan's
    chunks, the Pallas kernel's operand tensor) vmaps this over tile keys,
    so the draws cannot diverge between impls.  The ``prng-discipline``
    checker enforces that no raw draw bypasses it."""
    return jax.random.uniform(key, (t, 2), jnp.float32)


def draw_sweep_uniforms(key: Array, n: int, t: int) -> Array:
    """The sweep's (n, t, 2) uniforms: one key per *real* tile.

    Defines the sweep's randomness contract.  ``sample_sweep`` draws the
    same values chunk-by-chunk inside its scan (per-key PRNG, so batching
    never changes them); the Pallas wrapper
    (``repro.kernels.lda_sample.ops``) materializes this tensor as the
    kernel operand — either way the draws are bit-identical and
    deliberately independent of any padding (split before pad).
    """
    keys = jax.random.split(key, n)
    return jax.vmap(functools.partial(tile_uniforms, t=t))(keys)


def sample_one_tile(
    phi_col: Array,          # (K,) int — this word's phi row
    phi_sum: Array,          # (K,) int — global per-topic totals
    token_doc: Array,        # (t,) int32 local doc ids
    token_mask: Array,       # (t,) bool
    z_old: Array,            # (t,) current topics (returned for padding slots)
    ell_counts: Array,       # (D, P) int
    ell_topics: Array,       # (D, P) int
    uniforms: Array,         # (t, 2) float32
    *,
    alpha: float,
    beta: float,
    num_words_total: int,
) -> tuple[Array, Array, Array]:
    """Sample new topics for every token of one word tile.

    Returns (z_new (t,) int, used_sparse (t,) bool, s_over_sq (t,) float32 —
    per-token S/(S+Q) sparse mass share, 0 on padding slots).
    """
    pstar = _pstar(phi_col, phi_sum, beta, num_words_total)     # (K,)
    local, bcum, pstar_total = search_tables(pstar)             # C5 tree
    Q = alpha * pstar_total                                     # C4, per tile

    # --- sparse side: p1 over the ELL rows of each token's doc -------------
    tpc = ell_topics[token_doc]                                 # (t, P)
    cnt = ell_counts[token_doc].astype(jnp.float32)             # (t, P)
    p1 = cnt * pstar[tpc]                                       # (t, P)
    p1_cum = prefix_sum(p1)
    S = p1_cum[:, -1]                                           # (t,)

    u1 = uniforms[:, 0]
    u2 = uniforms[:, 1]
    use_sparse = u1 * (S + Q) < S

    # sparse draw: search the P-entry prefix sums (P <= K_d bound)
    t_sparse = u2 * S
    j = jnp.minimum(jnp.sum((p1_cum <= t_sparse[:, None]).astype(jnp.int32),
                            axis=1), tpc.shape[1] - 1)
    k_sparse = jnp.take_along_axis(tpc, j[:, None], axis=1)[:, 0].astype(jnp.int32)

    # dense draw: two-level blocked search over p* (C5)
    k_dense = blocked_draw(local, bcum, u2 * pstar_total)

    z_new = jnp.where(use_sparse, k_sparse, k_dense).astype(z_old.dtype)
    z_new = jnp.where(token_mask, z_new, z_old)
    s_over_sq = jnp.where(token_mask, S / jnp.maximum(S + Q, 1e-30), 0.0)
    return z_new, use_sparse & token_mask, s_over_sq


def sample_sweep(
    phi_vk: Array,           # (V_local, K) int — phi shard/replica, word-major
    phi_sum: Array,          # (K,) int — *global* per-topic totals
    tile_word: Array,        # (n,) int32 — local word id per tile
    token_doc: Array,        # (n, t) int32
    token_mask: Array,       # (n, t) bool
    z: Array,                # (n, t) int — current assignments
    ell_counts: Array,       # (D, P)
    ell_topics: Array,       # (D, P)
    key: Array,
    *,
    alpha: float,
    beta: float,
    num_words_total: int,
    tiles_per_step: int = 64,
) -> tuple[Array, SamplerStats]:
    """Full delayed-count sweep: all tiles sampled against frozen counts.

    scan over chunks of tiles (bounds working-set memory, mirrors the
    streaming WorkSchedule2 structure) with a vmap over tiles inside each
    chunk (the paper's "thousands of concurrent samplers").
    """
    n, t = z.shape
    # Per-tile keys split over the *unpadded* tile count so the draws are a
    # function of (key, corpus) only: jax.random.split is not prefix-stable,
    # so splitting after padding would make every draw depend on
    # tiles_per_step through n_pad.  Padding tiles reuse key 0 (fully
    # masked).  Uniforms are drawn per chunk inside the scan — only keys
    # cross the scan boundary, keeping the working set chunk-sized; the
    # Pallas sweep derives the bit-identical (n, t, 2) tensor via
    # ``draw_sweep_uniforms``.
    keys = jax.random.split(key, n)
    n_pad = -n % tiles_per_step
    if n_pad:  # pad with masked-out tiles of word 0 (static at trace time)
        tile_word = jnp.concatenate([tile_word, jnp.zeros(n_pad, tile_word.dtype)])
        token_doc = jnp.concatenate([token_doc, jnp.zeros((n_pad, t), token_doc.dtype)])
        token_mask = jnp.concatenate([token_mask, jnp.zeros((n_pad, t), bool)])
        z = jnp.concatenate([z, jnp.zeros((n_pad, t), z.dtype)])
        keys = jnp.concatenate([keys, jnp.repeat(keys[:1], n_pad, axis=0)])
    steps = (n + n_pad) // tiles_per_step

    def chunk(carry, inp):
        tw, td, tm, zc, kc = inp
        unif = jax.vmap(functools.partial(tile_uniforms, t=t))(kc)
        phi_cols = phi_vk[tw]                                   # (c, K) gather
        z_new, sp, ssq = jax.vmap(
            functools.partial(
                sample_one_tile,
                alpha=alpha, beta=beta, num_words_total=num_words_total,
            ),
            in_axes=(0, None, 0, 0, 0, None, None, 0),
        )(phi_cols, phi_sum, td, tm, zc, ell_counts, ell_topics, unif)
        return carry, (z_new, sp.sum(), ssq.sum(), (tm.sum()))

    xs = (
        tile_word.reshape(steps, tiles_per_step),
        token_doc.reshape(steps, tiles_per_step, t),
        token_mask.reshape(steps, tiles_per_step, t),
        z.reshape(steps, tiles_per_step, t),
        keys.reshape(steps, tiles_per_step),
    )
    _, (z_chunks, sp_counts, ssq_sums, tok_counts) = jax.lax.scan(chunk, 0, xs)
    z_new = z_chunks.reshape(n + n_pad, t)[:n]
    total = jnp.maximum(tok_counts.sum(), 1)
    stats = SamplerStats(
        sparse_frac=sp_counts.sum() / total,
        mean_s_over_sq=ssq_sums.sum() / total,
        row_width_share=jnp.float32(1),
    )
    return z_new, stats
