"""The Table 1 work count: by hand on a tiny corpus, and independent of
the tiling."""
from __future__ import annotations

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repository root on the path)
from bench.drivers import train
from bench.work import lda_sample as work


def tiny_state(tile_tokens: int):
    """A hand-made corpus, its planted topics and a tiled layout."""
    # doc 0: words 0,0,1 topics 3,3,5; doc 1: words 1,2 topics 5,6
    d = np.array([0, 0, 0, 1, 1], np.int32)
    w = np.array([0, 0, 1, 1, 2], np.int32)
    z = np.array([3, 3, 5, 5, 6], np.int32)
    corpus = train.Corpus(d, w, 2, 4)
    # word-major tiles of ``tile_tokens`` slots
    slots = []
    for word in (0, 1, 2):
        toks = np.flatnonzero(w == word)
        for a in range(0, len(toks), tile_tokens):
            slots.append((word, toks[a:a + tile_tokens]))
    n = len(slots)
    layout = {"tile_word": np.array([s[0] for s in slots], np.int32),
              "token_doc": np.zeros((n, tile_tokens), np.int32),
              "token_mask": np.zeros((n, tile_tokens), bool),
              "token_uid": np.full((n, tile_tokens), -1, np.int32)}
    z_tiled = np.zeros((n, tile_tokens), np.int32)
    for i, (_, toks) in enumerate(slots):
        m = len(toks)
        layout["token_doc"][i, :m] = d[toks]
        layout["token_mask"][i, :m] = True
        layout["token_uid"][i, :m] = toks
        z_tiled[i, :m] = z[toks]
    return {"corpus": corpus, "layout": layout, "z_end": z_tiled,
            "cfg": {"num_topics": 8}}


def test_hand_count():
    st = tiny_state(4)
    K = 8
    # K_d: doc 0 has topics {3, 5} -> 2, doc 1 {5, 6} -> 2; every token's
    # document has 2 live topics: sum over tokens 5 * 2 = 10
    kd_sum, distinct = 10, 3
    ops = (4 + 6) * kd_sum + (2 + 3) * K * distinct
    byts = (12 + 20) * kd_sum + (8 + 16) * K * distinct
    got = train.work_counts(st)
    assert got["sampler"] == (ops, byts)
    assert got["plan"] == (5, 8 * 5 + 8 * 4)
    assert got["count_update"] == (10, 20 * 5)
    assert got["mean_kd_per_token"] == 2.0


@pytest.mark.parametrize("t", [1, 2, 3])
def test_count_does_not_depend_on_tiling(t):
    assert train.work_counts(tiny_state(t)) == train.work_counts(
        tiny_state(4))


def test_bound_is_the_larger_of_the_two():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.bound_seconds(50, 100, peaks) == 10.0
    assert work.bound_seconds(5000, 100, peaks) == 50.0
