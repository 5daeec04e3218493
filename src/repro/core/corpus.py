"""Corpus representation, workload partition and word-major tiling.

Implements the paper's (CuLDA_CGS) data layout decisions:

* C1 (§4): partition-by-document, balanced **by token count** (longest-
  processing-time round robin) so that every device shard carries the same
  number of tokens, not the same number of documents.
* C6 (§6.1.2): tokens sorted in **word-first order** and grouped into fixed
  size *tiles*: one tile = (one word, up to ``tile_tokens`` tokens of that
  word).  On the GPU a tile was a thread block sharing the word's p* index
  tree through shared memory; on TPU a tile is one Pallas grid step whose p*
  column lives in VMEM.  Words with more tokens than a tile span several
  tiles (the paper's heavy-word splitting) and heavy words come first
  (long-tail avoidance).
* C7 (§6.1.3): topic assignments and ELL column ids are stored as int16
  (K < 2**16); per-token doc ids as int32.

All host-side preprocessing is numpy; the result is a pytree of jnp arrays
(``TiledCorpusShard``) that is static for the whole training run.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

TOPIC_DTYPE = np.int16  # C7: K < 2**16
COUNT_DTYPE = np.int32


@dataclasses.dataclass(frozen=True)
class Corpus:
    """A bag-of-words corpus in token-stream form (host side, numpy)."""

    doc_ids: np.ndarray  # (T,) int32 — document of each token
    word_ids: np.ndarray  # (T,) int32 — word of each token
    num_docs: int
    num_words: int

    @property
    def num_tokens(self) -> int:
        return int(self.doc_ids.shape[0])

    def doc_lengths(self) -> np.ndarray:
        return np.bincount(self.doc_ids, minlength=self.num_docs)

    def validate(self) -> None:
        assert self.doc_ids.shape == self.word_ids.shape
        assert self.doc_ids.min() >= 0 and self.doc_ids.max() < self.num_docs
        assert self.word_ids.min() >= 0 and self.word_ids.max() < self.num_words


def read_uci_bow(path: str, max_docs: int | None = None) -> Corpus:
    """Read the UCI bag-of-words format that NYTimes/PubMed ship in.

    Line 1: D, line 2: W, line 3: NNZ, then ``doc word count`` triples
    (1-indexed).
    """
    with open(path) as f:
        num_docs = int(f.readline())
        num_words = int(f.readline())
        f.readline()  # NNZ
        triples = np.loadtxt(f, dtype=np.int64).reshape(-1, 3)
    if max_docs is not None:
        triples = triples[triples[:, 0] <= max_docs]
        num_docs = min(num_docs, max_docs)
    docs = np.repeat(triples[:, 0] - 1, triples[:, 2]).astype(np.int32)
    words = np.repeat(triples[:, 1] - 1, triples[:, 2]).astype(np.int32)
    return Corpus(docs, words, num_docs, num_words)


# ---------------------------------------------------------------------------
# C1: balanced partition-by-document
# ---------------------------------------------------------------------------

def partition_by_document(corpus: Corpus, num_shards: int) -> list[np.ndarray]:
    """Assign documents to shards, balancing **token** counts (paper §4).

    Longest-processing-time (LPT) greedy: sort docs by length descending,
    place each in the currently lightest shard.  Returns, per shard, the
    sorted array of global document ids it owns.
    """
    lengths = corpus.doc_lengths()
    order = np.argsort(-lengths, kind="stable")
    loads = np.zeros(num_shards, dtype=np.int64)
    assign = np.empty(corpus.num_docs, dtype=np.int32)
    # LPT via a simple loop over docs (host-side, one-off).  For very large D
    # fall back to a sorted round-robin which is O(D) and within ~1% balance.
    if corpus.num_docs <= 2_000_000:
        import heapq

        heap = [(0, s) for s in range(num_shards)]
        heapq.heapify(heap)
        for d in order:
            load, s = heapq.heappop(heap)
            assign[d] = s
            heapq.heappush(heap, (load + int(lengths[d]), s))
        del heap
    else:  # serpentine round-robin on the sorted order
        for i, d in enumerate(order):
            r = i % (2 * num_shards)
            assign[d] = r if r < num_shards else 2 * num_shards - 1 - r
    for s in range(num_shards):
        loads[s] = lengths[assign == s].sum()
    return [np.sort(np.nonzero(assign == s)[0]).astype(np.int32) for s in range(num_shards)]


# ---------------------------------------------------------------------------
# C6: word-major tiling
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TiledCorpusShard:
    """One shard's tokens in word-major tiles (device-ready pytree).

    Shapes (``n`` = number of tiles, ``t`` = tile_tokens):
      tile_word:    (n,)   int32 — the word every token in the tile shares
      token_doc:    (n, t) int32 — local (shard) document id per token
      token_mask:   (n, t) bool  — False for padding slots
      tile_first:   (n,)   bool  — True on the first tile of each word run
      doc_length:   (d,)   int32 — local doc lengths (for α terms / checks)
      doc_global:   (d,)   int32 — local→global doc id map
      num_tokens:   int          — real (unpadded) token count
    """

    tile_word: jnp.ndarray
    token_doc: jnp.ndarray
    token_mask: jnp.ndarray
    tile_first: jnp.ndarray
    doc_length: jnp.ndarray
    doc_global: jnp.ndarray
    token_uid: jnp.ndarray  # (n, t) int32 — canonical corpus token index (-1 pad)
    num_tokens: int
    num_words: int          # local phi rows (V shard size in 2D mode)
    num_docs_local: int
    num_words_total: int = 0  # global vocabulary size (Eq. 1's V)

    def tree_flatten(self):
        children = (self.tile_word, self.token_doc, self.token_mask,
                    self.tile_first, self.doc_length, self.doc_global,
                    self.token_uid)
        aux = (self.num_tokens, self.num_words, self.num_docs_local,
               self.num_words_total)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


import jax.tree_util as _jtu  # noqa: E402

_jtu.register_pytree_node(
    TiledCorpusShard, TiledCorpusShard.tree_flatten, TiledCorpusShard.tree_unflatten
)


def num_tiles(word_ids: np.ndarray, num_words: int, tile_tokens: int) -> int:
    """Tiles the word-major tiling of these tokens takes: each word's
    tokens fill ``ceil(count / tile_tokens)`` tiles of their own."""
    counts = np.bincount(word_ids, minlength=num_words)
    return int((-(-counts // tile_tokens)).sum())


def _stable_argsort_ranks(rank: np.ndarray) -> np.ndarray:
    """``np.argsort(rank, kind="stable")`` for non-negative ranks below
    2**32, as two stable 16-bit radix passes (low half, then high half)."""
    rank = rank.astype(np.uint32)
    low = np.argsort((rank & 0xFFFF).astype(np.uint16), kind="stable")
    high = np.argsort((rank[low] >> 16).astype(np.uint16), kind="stable")
    return low[high]


def tile_shard_host(
    corpus: Corpus,
    doc_ids_of_shard: np.ndarray,
    tile_tokens: int = 256,
    pad_tiles_to: int | None = None,
    token_uid: np.ndarray | None = None,
    num_words_total: int | None = None,
) -> TiledCorpusShard:
    """``tile_shard`` with numpy fields, left on the host (for a mesh,
    whose devices each receive only their own shard)."""
    sel = np.isin(corpus.doc_ids, doc_ids_of_shard)
    docs = corpus.doc_ids[sel]
    words = corpus.word_ids[sel]
    uid = (np.nonzero(sel)[0].astype(np.int32) if token_uid is None
           else np.asarray(token_uid, dtype=np.int32)[sel])
    # local doc ids
    doc_global = np.asarray(doc_ids_of_shard, dtype=np.int32)
    remap = np.full(corpus.num_docs, -1, dtype=np.int32)
    remap[doc_global] = np.arange(len(doc_global), dtype=np.int32)
    docs_local = remap[docs]

    # word-first sort; heavy words first, stable within word
    counts = np.bincount(words, minlength=corpus.num_words)
    heavy_rank = np.argsort(np.argsort(-counts, kind="stable"), kind="stable")
    order = _stable_argsort_ranks(heavy_rank[words])
    docs_local = docs_local[order]
    words_sorted = words[order]
    uid_sorted = uid[order]

    # cut into tiles: a tile never mixes words.  Each run of one word
    # fills ceil(run / t) tiles from its first, so token i of a run sits
    # in slot first_tile * t + i, and slots rise with the sorted order.
    T = len(words_sorted)
    run_start = np.flatnonzero(np.diff(words_sorted, prepend=-1) != 0)
    run_len = np.diff(np.append(run_start, T))
    run_tiles = -(-run_len // tile_tokens)
    first_tile = np.cumsum(run_tiles) - run_tiles
    n = int(run_tiles.sum())
    n_pad = pad_tiles_to if pad_tiles_to is not None else n
    assert n_pad >= n, f"pad_tiles_to={n_pad} < required {n}"

    tile_word = np.zeros(n_pad, dtype=np.int32)
    token_doc = np.zeros((n_pad, tile_tokens), dtype=np.int32)
    token_mask = np.zeros((n_pad, tile_tokens), dtype=bool)
    tile_first = np.zeros(n_pad, dtype=bool)
    tok_uid = np.full((n_pad, tile_tokens), -1, dtype=np.int32)
    tile_word[:n] = np.repeat(words_sorted[run_start], run_tiles)
    tile_first[first_tile] = True
    slot = np.repeat(first_tile * tile_tokens - run_start, run_len) + np.arange(T)
    token_mask.reshape(-1)[slot] = True
    token_doc[token_mask] = docs_local
    tok_uid[token_mask] = uid_sorted
    # padding tiles alias the LAST real word with tile_first=False so that
    # accumulation kernels (phi_update) neither re-zero a row nor add to it
    if n and n_pad > n:
        tile_word[n:] = tile_word[n - 1]

    doc_length = np.bincount(docs_local, minlength=len(doc_global)).astype(np.int32)
    return TiledCorpusShard(
        tile_word=tile_word,
        token_doc=token_doc,
        token_mask=token_mask,
        tile_first=tile_first,
        doc_length=doc_length,
        doc_global=doc_global,
        token_uid=tok_uid,
        num_tokens=int(len(docs_local)),
        num_words=corpus.num_words,
        num_docs_local=int(len(doc_global)),
        num_words_total=(corpus.num_words if num_words_total is None
                         else num_words_total),
    )


def tile_shard(
    corpus: Corpus,
    doc_ids_of_shard: np.ndarray,
    tile_tokens: int = 256,
    pad_tiles_to: int | None = None,
    token_uid: np.ndarray | None = None,
    num_words_total: int | None = None,
) -> TiledCorpusShard:
    """Build the word-major tiling for one shard (paper §6.1.2).

    Heavy words (most tokens) are tiled first — the GPU scheduler ran the
    biggest thread blocks first to avoid the long tail; at pod scale the same
    ordering keeps the scan's trailing tiles cheap.

    ``token_uid`` maps this shard's tokens back to canonical corpus indices
    (for elastic checkpoints); defaults to the corpus positions of the
    selected tokens.
    """
    return jax.tree.map(jnp.asarray, tile_shard_host(
        corpus, doc_ids_of_shard, tile_tokens, pad_tiles_to, token_uid,
        num_words_total))


def tile_corpus(
    corpus: Corpus, num_shards: int, tile_tokens: int = 256
) -> list[TiledCorpusShard]:
    """Partition + tile: shards padded to a common tile count so they can be
    stacked on a mesh axis (SPMD requires identical per-device shapes)."""
    parts = partition_by_document(corpus, num_shards)
    n_max = max(num_tiles(corpus.word_ids[np.isin(corpus.doc_ids, p)],
                          corpus.num_words, tile_tokens) for p in parts)
    return [tile_shard(corpus, p, tile_tokens, n_max) for p in parts]


def ell_capacity(corpus: Corpus, num_topics: int, quantile: float = 1.0) -> int:
    """Upper bound for distinct topics per document (the ELL pad width P).

    ``quantile``<1 gives the bucketed variant's small-P capacity; 1.0 is the
    exact bound min(K, max doc length).
    """
    lengths = corpus.doc_lengths()
    q = int(np.quantile(lengths, quantile)) if quantile < 1.0 else int(lengths.max())
    cap = max(1, min(num_topics, q))
    # round up to a friendly lane multiple
    for mult in (8, 16, 32, 64, 128):
        if cap <= mult:
            return mult
    return int(np.ceil(cap / 128) * 128)
