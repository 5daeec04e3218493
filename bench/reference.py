"""Plain reference of the LDA semantics the benchmark checks the program by.

Written from the model (the paper's Eq. 1 with delayed counts and the S/Q
split), in numpy, importing nothing of the program.  Every function takes
the arithmetic type as ``dtype``: ``float64`` is the reference, and
``bfloat16``, the precision below the configuration's ``float32``, is the
control that a sound check must refuse.

A draw, for a token of word w in document d, given the doc-topic counts
theta_d, the topic-word counts phi and two uniforms (u1, u2):

    p*(k) = (phi[w, k] + beta) / (phi_sum[k] + V beta)
    S     = sum over the doc's live topics of theta_dk p*(k)
    Q     = alpha sum_k p*(k)
    sparse when u1 (S + Q) < S: the first live topic, in order of count
        (largest first, ties by topic id), whose running sum of
        theta_dk p*(k) exceeds u2 S;
    else: the first topic whose running sum of p*(k) exceeds u2 sum p*.
"""
from __future__ import annotations

import numpy as np


def as_dtype(x, dtype):
    return np.asarray(x).astype(dtype)


def pstar(phi_rows, phi_sum, beta: float, num_words: int, dtype):
    """(n, K) p* of the given phi rows."""
    num = as_dtype(as_dtype(phi_rows, np.float64) + beta, dtype)
    den = as_dtype(as_dtype(phi_sum, np.float64) + beta * num_words, dtype)
    return (num / den).astype(dtype)


def cumsum(x, dtype):
    """Running sum along the last axis, every partial sum in ``dtype``."""
    return np.cumsum(as_dtype(x, dtype), axis=-1, dtype=dtype)


def first_above(cum, target) -> np.ndarray:
    """Index of the first running sum above ``target`` (clamped)."""
    j = (cum <= target[:, None]).sum(axis=-1)
    return np.minimum(j, cum.shape[-1] - 1)


def ell_order(theta_rows: np.ndarray):
    """Live topics of each row, count descending and ties by topic id,
    padded with zero counts: ``(counts (n, P), topics (n, P))``."""
    n, K = theta_rows.shape
    order = np.lexsort((np.broadcast_to(np.arange(K), (n, K)), -theta_rows),
                       axis=-1)
    counts = np.take_along_axis(theta_rows, order, axis=-1)
    P = max(int((theta_rows > 0).sum(axis=-1).max(initial=0)), 1)
    return counts[:, :P], order[:, :P]


def draw(ps, counts, topics, u1, u2, alpha: float, dtype, dense_cum=None):
    """One draw for each of n tokens.

    ps (n, K) p* of each token's word; counts/topics (n, P) its doc's live
    topics in ELL order; u1, u2 (n,) float32 uniforms; ``dense_cum`` the
    running sums of ``ps`` when the caller has them."""
    ps = as_dtype(ps, dtype)
    if dense_cum is None:
        dense_cum = cumsum(ps, dtype)
    total = dense_cum[:, -1]
    p1 = as_dtype(counts, dtype) * np.take_along_axis(ps, topics, axis=-1)
    p1_cum = cumsum(p1, dtype)
    S = p1_cum[:, -1]
    Q = (as_dtype(alpha, dtype) * total).astype(dtype)
    u1, u2 = as_dtype(u1, dtype), as_dtype(u2, dtype)
    use_sparse = (u1 * (S + Q)).astype(dtype) < S
    k_sparse = np.take_along_axis(
        topics, first_above(p1_cum, (u2 * S).astype(dtype))[:, None],
        axis=-1)[:, 0]
    k_dense = first_above(dense_cum, (u2 * total).astype(dtype))
    return np.where(use_sparse, k_sparse, k_dense).astype(np.int32)


def topic_counts(rows: np.ndarray, topics: np.ndarray, n_rows: int,
                 num_topics: int) -> np.ndarray:
    """(n_rows, K) int64 counts of ``topics`` by ``rows``."""
    flat = rows.astype(np.int64) * num_topics + topics.astype(np.int64)
    return np.bincount(flat, minlength=n_rows * num_topics).reshape(
        n_rows, num_topics)


def fold_in(phi_rows, phi_sum, z0, uniforms, alpha: float, beta: float,
            num_words: int, burn_in: int, samples: int, dtype):
    """Fold-in Gibbs chain of one document against frozen phi.

    phi_rows (n, K) phi of its tokens; z0 (n,) initial topics; uniforms
    (burn_in + samples, n, 2).  Every sweep draws all tokens against the
    sweep-start doc-topic counts.  Returns the (K,) int64 sum of the doc's
    topic counts over the ``samples`` kept sweeps."""
    K = len(phi_sum)
    ps = pstar(phi_rows, phi_sum, beta, num_words, dtype)
    dense_cum = cumsum(ps, dtype)
    n = len(z0)
    zero = np.zeros(n, np.int64)
    z = np.asarray(z0)
    tsum = np.zeros(K, np.int64)
    for s in range(burn_in + samples):
        theta = topic_counts(zero, z, 1, K)
        counts, topics = ell_order(theta)
        z = draw(ps, np.broadcast_to(counts, (n, counts.shape[1])),
                 np.broadcast_to(topics, (n, topics.shape[1])),
                 uniforms[s, :, 0], uniforms[s, :, 1], alpha, dtype,
                 dense_cum)
        if s >= burn_in:
            tsum += topic_counts(zero, z, 1, K)[0]
    return tsum
