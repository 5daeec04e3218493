"""Planted-topic LDA corpora and request documents, made from a seed.

The configurations' data, kept apart from the program's own
``repro.data.synthetic`` so that no change to the program moves the
yardstick.  One generative process serves both configurations:

* document lengths are lognormal with the configuration's mean and sigma,
  drawn from the configuration's ``length_seed`` alone, so every seed
  carries the same lengths and the same number of tokens;
* each document draws a topic mix from a symmetric Dirichlet over K, and
  each token its topic from that mix (drawn exactly as the
  Dirichlet-multinomial Polya urn, vectorised over documents);
* each token's word comes, with probability ``global_share``, from a
  global Zipf over V (word id = rank), and otherwise from its topic's own
  Zipf over a seeded permutation of V: rank r maps to word
  ``(a_k * r + b_k) mod V`` with ``a_k`` prime to V.

A training corpus is one corpus per configuration, drawn from its
``corpus_seed``, under a relabelling drawn from the run's seed: documents,
words and topics are permuted.  So every seed gives the program the same
amount of work in the same shapes (the number of word tiles follows from
the multiset of word counts), and no seed compiles a program another did
not.

``topic_perm`` gives the permutation parameters; ``expected_phi`` builds
the planted model's expected topic-word counts on the device from them.
"""
from __future__ import annotations

import math

import numpy as np

MUL_SPLIT = 9  # bits of the low half in ``mulmod``; V < 2**17 keeps int32


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for ``seed`` (any size) and a stream tag."""
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def lengths(cfg: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lognormal document lengths (at least 1) for ``cfg``."""
    a = cfg["assumed"]
    sigma = float(a["length_sigma"])
    mu = math.log(float(a["length_mean"])) - sigma * sigma / 2
    return np.maximum(1, np.rint(rng.lognormal(mu, sigma, n))).astype(
        np.int64)


def fixed_lengths(cfg: dict, n: int) -> np.ndarray:
    """The configuration's ``n`` document lengths, the same for every
    seed."""
    return lengths(cfg, n, rng_for(int(cfg["assumed"]["length_seed"]), n))


def zipf_cdf(num_words: int, exponent: float) -> np.ndarray:
    p = np.arange(1, num_words + 1, dtype=np.float64) ** -exponent
    c = np.cumsum(p)
    return c / c[-1]


def topic_perm(num_topics: int, num_words: int, seed: int):
    """Per-topic affine permutations of V: ``(a, b)``, int64 (K,) each,
    with every ``a`` prime to V."""
    rng = rng_for(seed, 2)
    a = rng.integers(1, num_words, num_topics)
    for i in range(num_topics):
        while math.gcd(int(a[i]), num_words) != 1:
            a[i] = rng.integers(1, num_words)
    b = rng.integers(0, num_words, num_topics)
    return a.astype(np.int64), b.astype(np.int64)


def topic_mix_draws(lens: np.ndarray, num_topics: int, concentration: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Topics of every token, document-major, each document's drawn from a
    symmetric Dirichlet(``concentration``) mix: the Polya urn, in which
    token i takes a fresh topic with probability a0 / (a0 + i) and else
    copies an earlier token of its document (a0 = K * concentration)."""
    a0 = num_topics * concentration
    starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
    order = np.argsort(-lens, kind="stable")          # longest first
    s_sorted, l_sorted = starts[order], lens[order]
    z = np.empty(int(lens.sum()), np.int32)
    n_active = len(lens)
    for i in range(int(lens.max())):
        while n_active and l_sorted[n_active - 1] <= i:
            n_active -= 1
        st = s_sorted[:n_active]
        fresh = rng.random(n_active) * (a0 + i) < a0
        new = rng.integers(0, num_topics, n_active)
        if i:
            prev = z[st + (rng.random(n_active) * i).astype(np.int64)]
            new = np.where(fresh, new, prev)
        z[st + i] = new
    return z


def words_for_topics(z: np.ndarray, cfg: dict, perm,
                     rng: np.random.Generator) -> np.ndarray:
    """A word for every token of topic ``z``."""
    V = int(cfg["num_words"])
    a = cfg["assumed"]
    cdf = zipf_cdf(V, float(a["zipf_exponent"]))
    rank = np.minimum(np.searchsorted(cdf, rng.random(len(z)), "right"),
                      V - 1)
    own = rng.random(len(z)) >= float(a["global_share"])
    pa, pb = perm
    zi = z.astype(np.int64)
    mapped = (pa[zi] * rank + pb[zi]) % V
    return np.where(own, mapped, rank).astype(np.int32)


def documents(cfg: dict, lens: np.ndarray, seed: int, stream: int):
    """Planted documents of the given lengths.

    Returns ``(doc_ids, word_ids, topics)``, int32 token arrays in
    document-major order (document d's tokens are contiguous)."""
    rng = rng_for(seed, 3, stream)
    K = int(cfg["num_topics"])
    z = topic_mix_draws(lens, K, float(cfg["assumed"]["doc_topic_prior"]),
                        rng)
    perm = topic_perm(K, int(cfg["num_words"]), seed)
    w = words_for_topics(z, cfg, perm, rng)
    d = np.repeat(np.arange(len(lens), dtype=np.int32), lens)
    return d, w, z


def training_corpus(cfg: dict, seed: int):
    """The configuration's training corpus of ``num_docs`` documents,
    relabelled by ``seed``: ``(doc_ids, word_ids, topics)``, int32,
    document-major."""
    base = int(cfg["assumed"]["corpus_seed"])
    D, V, K = (int(cfg[k]) for k in ("num_docs", "num_words", "num_topics"))
    d, w, z = documents(cfg, fixed_lengths(cfg, D), base, 0)
    rng = rng_for(seed, 4)
    new_doc, new_word, new_topic = (rng.permutation(n).astype(np.int32)
                                    for n in (D, V, K))
    order = np.argsort(new_doc[d], kind="stable")
    return (new_doc[d][order], new_word[w][order], new_topic[z][order])


def request_docs(cfg: dict, n: int, seed: int) -> list[np.ndarray]:
    """``n`` unseen documents of the planted model (one word-id array
    each) of the configuration's ``n`` fixed lengths, in their order."""
    lens = fixed_lengths(cfg, n)
    _, w, _ = documents(cfg, lens, seed, 1)
    ends = np.cumsum(lens)
    return [w[e - m:e] for e, m in zip(ends, lens)]


def mulmod(a, x, m: int):
    """``(a * x) mod m`` in int32 for ``a, x < m < 2**17`` (jnp or numpy)."""
    hi, lo = x >> MUL_SPLIT, x & ((1 << MUL_SPLIT) - 1)
    return (((a * hi) % m) * (1 << MUL_SPLIT) + a * lo) % m


def expected_phi(cfg: dict, seed: int, num_tokens: int):
    """The planted model's expected topic-word counts at ``num_tokens``
    tokens, built on the device in one jitted call: ``(phi (V, K) int32,
    phi_sum (K,) int32)``."""
    import jax
    import jax.numpy as jnp

    V, K = int(cfg["num_words"]), int(cfg["num_topics"])
    if V >= 1 << 17:
        raise ValueError(f"V={V}: mulmod keeps int32 only for V < 2**17")
    a, b = topic_perm(K, V, seed)
    a_inv = np.array([pow(int(x), -1, V) for x in a], np.int32)
    share = float(cfg["assumed"]["global_share"])
    expo = float(cfg["assumed"]["zipf_exponent"])
    per_topic = num_tokens / K

    @jax.jit
    def build(a_inv, b):
        ranks = jnp.arange(1, V + 1, dtype=jnp.float32)
        p = ranks ** -expo
        p = p / p.sum()
        w = jnp.arange(V, dtype=jnp.int32)[:, None]
        r = mulmod(a_inv[None, :], (w - b[None, :]) % V, V)   # (V, K) rank
        pw = share * p[:, None] + (1 - share) * p[r]
        phi = jnp.rint(per_topic * pw).astype(jnp.int32)
        return phi, phi.sum(axis=0)

    return build(jnp.asarray(a_inv), jnp.asarray(b.astype(np.int32)))
