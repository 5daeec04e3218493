"""The configurations' generator: deterministic per seed, the stated
shapes, the same work for every seed."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the repository root on the path)
from bench import generator

ROOT = Path(__file__).resolve().parents[2]


def config(name: str, depth: float) -> dict:
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    cfg["num_docs"] = int(cfg["published"]["num_docs"] * depth)
    return cfg


@pytest.mark.parametrize("name,depth", [("nytimes", 0.005),
                                        ("pubmed", 0.0002)])
def test_shapes_and_mean_length(name, depth):
    cfg = config(name, depth)
    d, w, z = generator.training_corpus(cfg, 3)
    D, V, K = cfg["num_docs"], cfg["num_words"], cfg["num_topics"]
    assert d.max() == D - 1 and np.all(np.diff(d) >= 0)
    assert 0 <= w.min() and w.max() < V and 0 <= z.min() and z.max() < K
    mean = len(d) / D
    assert abs(mean / cfg["assumed"]["length_mean"] - 1) < 0.05
    pub = cfg["published"]
    assert abs(mean / (pub["num_tokens"] / pub["num_docs"]) - 1) < 0.05
    assert V == pub["num_words"] and K == 1024


def test_deterministic_per_seed_and_same_work_across_seeds():
    cfg = config("nytimes", 0.002)
    a = generator.training_corpus(cfg, 2**33 + 1)
    b = generator.training_corpus(cfg, 2**33 + 1)
    c = generator.training_corpus(cfg, 2**33 + 2)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert len(a[0]) == len(c[0])                 # same multiset of lengths
    assert np.array_equal(np.sort(np.bincount(a[0])),
                          np.sort(np.bincount(c[0])))
    assert not np.array_equal(a[1], c[1])
    # relabelled, not redrawn: the word counts (so the tiling) are alike
    V = cfg["num_words"]
    assert np.array_equal(np.sort(np.bincount(a[1], minlength=V)),
                          np.sort(np.bincount(c[1], minlength=V)))


def test_documents_are_topic_sparse_and_zipf_skewed():
    cfg = config("nytimes", 0.002)
    d, w, z = generator.training_corpus(cfg, 5)
    K = cfg["num_topics"]
    live = np.unique(d.astype(np.int64) * K + z) // K
    kd = np.bincount(live)
    # a Dirichlet(0.1) mix over 1024 topics keeps far fewer live topics
    # than tokens in a long document
    long = np.bincount(d) > 600
    assert (kd[long] < np.bincount(d)[long] / 2).all()
    counts = np.bincount(w, minlength=cfg["num_words"])
    assert counts.max() > 50 * np.median(counts[counts > 0])


def test_mulmod_and_topic_permutations():
    V = 101636
    a, b = generator.topic_perm(16, V, 7)
    r = np.arange(V, dtype=np.int64)
    for k in range(16):
        w = (a[k] * r + b[k]) % V
        assert np.unique(w).size == V
        inv = pow(int(a[k]), -1, V)
        np.testing.assert_array_equal(
            generator.mulmod(np.int64(inv), (w - b[k]) % V, V), r)


def test_expected_phi_matches_a_host_count():
    cfg = config("nytimes", 0.001)
    cfg["num_words"], cfg["num_topics"] = 3000, 64
    phi, phi_sum = generator.expected_phi(cfg, 9, 10**6)
    phi = np.asarray(phi)
    a, b = generator.topic_perm(64, 3000, 9)
    p = np.arange(1, 3001, dtype=np.float64) ** -1.1
    p /= p.sum()
    rank = np.empty((3000, 64), np.int64)
    for k in range(64):
        rank[(a[k] * np.arange(3000) + b[k]) % 3000, k] = np.arange(3000)
    want = 10**6 / 64 * (0.5 * p[:, None] + 0.5 * p[rank])
    assert np.abs(phi - want).max() <= 1.0
    np.testing.assert_array_equal(np.asarray(phi_sum), phi.sum(0))


def test_request_docs_keep_lengths_and_draw_words():
    cfg = config("nytimes", 0.001)
    a = generator.request_docs(cfg, 300, 1)
    b = generator.request_docs(cfg, 300, 2)
    assert [len(x) for x in a] == [len(x) for x in b]
    assert not all(np.array_equal(x, y) for x, y in zip(a, b))
