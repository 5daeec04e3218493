"""Corpus layer: partition balance (C1), word-major tiling (C6), uid maps."""
import numpy as np
import pytest

try:  # property tests need hypothesis; the rest of the module does not
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.core.corpus import (Corpus, ell_capacity, partition_by_document,
                               tile_corpus)


def make_corpus(doc_ids, word_ids, D, V):
    return Corpus(np.asarray(doc_ids, np.int32), np.asarray(word_ids, np.int32),
                  D, V)


class TestPartition:
    def test_balanced_by_tokens(self, zipf_corpus_small):
        parts = partition_by_document(zipf_corpus_small, 4)
        lengths = zipf_corpus_small.doc_lengths()
        loads = [lengths[p].sum() for p in parts]
        assert max(loads) - min(loads) <= lengths.max()  # LPT bound
        # every doc exactly once
        all_docs = np.sort(np.concatenate(parts))
        assert (all_docs == np.arange(zipf_corpus_small.num_docs)).all()

    def test_single_shard_identity(self, tiny_corpus):
        (part,) = partition_by_document(tiny_corpus, 1)
        assert (part == np.arange(tiny_corpus.num_docs)).all()


class TestTiling:
    def test_tiles_never_mix_words(self, zipf_corpus_small):
        sh = tile_corpus(zipf_corpus_small, 1, tile_tokens=16)[0]
        # tokens in a tile all belong to tile_word: verified via uid lookup
        uid = np.asarray(sh.token_uid)
        mask = np.asarray(sh.token_mask)
        words = np.asarray(sh.tile_word)
        for i in range(uid.shape[0]):
            toks = uid[i][mask[i]]
            if len(toks):
                assert (zipf_corpus_small.word_ids[toks] == words[i]).all()

    def test_uids_form_permutation(self, zipf_corpus_small):
        sh = tile_corpus(zipf_corpus_small, 1, tile_tokens=16)[0]
        uid = np.asarray(sh.token_uid)[np.asarray(sh.token_mask)]
        assert len(np.unique(uid)) == zipf_corpus_small.num_tokens

    def test_heavy_words_first(self, zipf_corpus_small):
        sh = tile_corpus(zipf_corpus_small, 1, tile_tokens=16)[0]
        counts = np.bincount(zipf_corpus_small.word_ids,
                             minlength=zipf_corpus_small.num_words)
        words = np.asarray(sh.tile_word)
        first = np.asarray(sh.tile_first)
        order = [counts[w] for w, f in zip(words, first) if f]
        assert order == sorted(order, reverse=True)

    def test_mask_matches_token_count(self, tiny_corpus):
        sh = tile_corpus(tiny_corpus, 1, tile_tokens=32)[0]
        assert int(np.asarray(sh.token_mask).sum()) == tiny_corpus.num_tokens

    def test_doc_lengths(self, tiny_corpus):
        sh = tile_corpus(tiny_corpus, 1, tile_tokens=32)[0]
        np.testing.assert_array_equal(np.asarray(sh.doc_length),
                                      tiny_corpus.doc_lengths())


if HAVE_HYPOTHESIS:
    @given(
        n_docs=st.integers(2, 12),
        n_words=st.integers(2, 20),
        n_tokens=st.integers(1, 300),
        tile=st.sampled_from([4, 16, 64]),
        shards=st.integers(1, 4),
        seed=st.integers(0, 10_000),
    )
    @settings(max_examples=25, deadline=None)
    def test_tiling_roundtrip_property(n_docs, n_words, n_tokens, tile, shards, seed):
        """Property: for any corpus, sharding+tiling preserves every token exactly
        once with its correct (doc, word) pair."""
        rng = np.random.default_rng(seed)
        corpus = make_corpus(rng.integers(0, n_docs, n_tokens),
                             rng.integers(0, n_words, n_tokens), n_docs, n_words)
        shards_list = tile_corpus(corpus, shards, tile)
        seen = []
        for sh in shards_list:
            uid = np.asarray(sh.token_uid)
            m = np.asarray(sh.token_mask)
            words = np.asarray(sh.tile_word)
            dl = np.asarray(sh.doc_global)
            docs_local = np.asarray(sh.token_doc)
            for i in range(uid.shape[0]):
                for j in range(uid.shape[1]):
                    if m[i, j]:
                        tok = uid[i, j]
                        seen.append(tok)
                        assert corpus.word_ids[tok] == words[i]
                        assert corpus.doc_ids[tok] == dl[docs_local[i, j]]
        assert sorted(seen) == list(range(n_tokens))
else:
    def test_tiling_roundtrip_property():
        pytest.importorskip("hypothesis")


def test_ell_capacity_bounds(tiny_corpus):
    P = ell_capacity(tiny_corpus, 8)
    assert P >= min(8, int(tiny_corpus.doc_lengths().max()))
    assert ell_capacity(tiny_corpus, 10_000) >= 8


def tile_by_loop(corpus, docs, t, n_pad):
    """The tiling written tile by tile: the word-major layout the
    vectorised ``tile_shard_host`` must reproduce field for field."""
    sel = np.isin(corpus.doc_ids, docs)
    d, w = corpus.doc_ids[sel], corpus.word_ids[sel]
    uid = np.nonzero(sel)[0]
    remap = np.full(corpus.num_docs, -1)
    remap[docs] = np.arange(len(docs))
    counts = np.bincount(w, minlength=corpus.num_words)
    rank = np.argsort(np.argsort(-counts, kind="stable"), kind="stable")
    order = np.argsort(rank[w], kind="stable")
    d, w, uid = remap[d][order], w[order], uid[order]
    out = dict(tile_word=np.zeros(n_pad, np.int32),
               token_doc=np.zeros((n_pad, t), np.int32),
               token_mask=np.zeros((n_pad, t), bool),
               tile_first=np.zeros(n_pad, bool),
               token_uid=np.full((n_pad, t), -1, np.int32))
    i, a = 0, 0
    while a < len(w):
        b = a
        while b < len(w) and w[b] == w[a]:
            b += 1
        for s in range(a, b, t):
            e = min(s + t, b)
            out["tile_word"][i] = w[a]
            out["tile_first"][i] = s == a
            out["token_doc"][i, :e - s] = d[s:e]
            out["token_mask"][i, :e - s] = True
            out["token_uid"][i, :e - s] = uid[s:e]
            i += 1
        a = b
    if i:
        out["tile_word"][i:] = out["tile_word"][i - 1]
    return out, i


@pytest.mark.parametrize("t", [1, 3, 16, 64])
def test_vectorised_tiling_matches_the_loop(t):
    from repro.core.corpus import num_tiles, tile_shard, tile_shard_host

    rng = np.random.default_rng(t)
    D, V, T = 30, 150, 2500
    # Zipf-like words so that heavy words span several tiles
    corpus = make_corpus(np.sort(rng.integers(0, D, T)),
                         np.minimum(rng.zipf(1.3, T) - 1, V - 1), D, V)
    docs = np.sort(rng.choice(D, 20, replace=False)).astype(np.int32)
    _, n = tile_by_loop(corpus, docs, t, T)
    want, _ = tile_by_loop(corpus, docs, t, n + 3)
    assert num_tiles(corpus.word_ids[np.isin(corpus.doc_ids, docs)], V,
                     t) == n
    host = tile_shard_host(corpus, docs, t, n + 3)
    dev = tile_shard(corpus, docs, t, n + 3)
    for k, v in want.items():
        np.testing.assert_array_equal(getattr(host, k), v, err_msg=k)
        np.testing.assert_array_equal(np.asarray(getattr(dev, k)), v,
                                      err_msg=k)
    assert isinstance(host.token_doc, np.ndarray)
    assert not isinstance(dev.token_doc, np.ndarray)


def test_empty_shard_tiles_to_padding_only():
    from repro.core.corpus import tile_shard_host

    corpus = make_corpus([0, 0, 1], [2, 2, 1], 3, 4)
    sh = tile_shard_host(corpus, np.array([2], np.int32), 4, 2)
    assert sh.num_tokens == 0 and not sh.token_mask.any()
    assert sh.tile_word.shape == (2,) and not sh.tile_first.any()
