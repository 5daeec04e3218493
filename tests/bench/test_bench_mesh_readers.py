"""The mesh cell's readers on a hand-built four-chip trace, and the phi
sync's byte count against the program's own counter."""
from __future__ import annotations

import pytest

import bench_tiny  # noqa: F401  (puts the repository root on the path)
from bench import spec, trace
from bench.work import lda_sync
from bench_tiny import ROOT

V, K, CHIPS = 101_636, 1024, 4
KIND = "TPU v5 lite"


def reader(name):
    return spec.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                            f"test_metric_{name.replace('.', '_')}").read


class Reading:
    def __init__(self, summary, iterations=2, work=None):
        self.trace = summary
        self.window = {"iterations": iterations}
        self.work = work if work is not None else {
            "sync": {"payload_bytes": lda_sync.payload_bytes(V, K),
                     "chips": CHIPS, "device_kind": KIND}}
        self.peaks = {}


def four_chips(sample_ns, sync_ns, stats_ns=10):
    """Two iterations a chip: sampling, then the phi sync and the stats'
    collectives, which end together on every chip."""
    ops = []
    for it in range(2):
        base = it * 15_000_000
        for d in range(CHIPS):
            s = base + sample_ns[d]
            ops += [trace.Op(base, s, "lda_sample", "a/lda.sample/k", d),
                    trace.Op(s, s + sync_ns[d], "all-reduce",
                             "a/lda.sync/psum", d),
                    trace.Op(s + sync_ns[d], s + sync_ns[d] + stats_ns,
                             "all-reduce.1", "a/lda.stats/psum", d)]
    return trace.Summary(ops, [], 0, 30_000_000, CHIPS)


def test_sync_ms_is_the_mean_over_chips_per_iteration():
    s = four_chips([4_000_000, 4_000_000, 4_000_000, 4_100_000],
                   [200_000, 200_000, 200_000, 100_000])
    assert reader("lda.sync_ms")(Reading(s)) == pytest.approx(0.175)


def test_sync_roofline_is_the_ring_bound_over_the_sync_time():
    s = four_chips([4_000_000] * 4, [6_244_608] * 4)
    bound = 2 * 3 / 4 * V * K * 4 / (1600e9 / 8)
    assert bound == pytest.approx(lda_sync.bound_seconds(V * K * 4, 4, KIND))
    got = reader("lda_sync_roofline")(Reading(s))
    assert got == pytest.approx(100 * bound / 6.244608e-3)
    assert 0 < got <= 100


def test_chip_skew_reads_the_work_outside_the_collectives():
    # chip 3 samples 10% longer than the rest, whose syncs wait for it
    s = four_chips([4_000_000, 4_000_000, 4_000_000, 4_400_000],
                   [500_000, 500_000, 500_000, 100_000])
    mean = (3 * 4_000_000 + 4_400_000) / 4
    assert reader("chip_skew.train")(Reading(s)) == pytest.approx(
        100 * (4_400_000 / mean - 1))
    even = four_chips([4_000_000] * 4, [100_000, 300_000, 200_000, 50_000])
    assert reader("chip_skew.train")(Reading(even)) == pytest.approx(0)


def test_readers_return_nothing_when_nothing_to_read():
    one = trace.Summary([trace.Op(0, 10, "x", "lda.sample/k", 0)], [], 0,
                        20, 1)
    assert reader("chip_skew.train")(Reading(one)) is None
    assert reader("lda.sync_ms")(Reading(one)) is None
    assert reader("lda_sync_roofline")(Reading(one)) is None
    synced = four_chips([4_000_000] * 4, [200_000] * 4)
    assert reader("lda_sync_roofline")(Reading(synced, work={})) is None


def test_sync_bytes_and_peak():
    assert lda_sync.payload_bytes(V, K) == V * K * 4 == 416_301_056
    assert lda_sync.wire_bytes_per_chip(100, 4) == 150
    assert lda_sync.ici_bytes_per_s(KIND) == 200e9
    with pytest.raises(KeyError, match="no interconnect peak"):
        lda_sync.ici_bytes_per_s("TPU v99 imaginary")


@pytest.mark.parametrize("compressed", [False, True])
def test_program_sync_counter_matches_the_benchmark_count(compressed):
    """The program's count of the bytes each chip all-reduces per
    iteration, from its shapes and dtypes, equals the benchmark's."""
    import jax
    import numpy as np

    from repro.core.corpus import Corpus
    from repro.core.trainer import LDAConfig
    from repro.distributed import partition

    rng = np.random.default_rng(3)
    T, D, Vs, Ks = 4000, 40, 300, 64
    corpus = Corpus(np.sort(rng.integers(0, D, T)).astype(np.int32),
                    rng.integers(0, Vs, T).astype(np.int32), D, Vs)
    cfg = LDAConfig(num_topics=Ks, tile_tokens=32, compressed_sync=compressed)
    mesh = jax.sharding.AbstractMesh((CHIPS,), ("data",))
    dl = partition.DistributedLDA(cfg, mesh, corpus, mode="1d")
    got = dl.counters["sync_bytes"]
    if compressed:
        heavy = np.asarray(dl._heavy).shape[1]
        assert got == lda_sync.payload_bytes(Vs, Ks, 2) + heavy * Ks * 4
    else:
        assert got == lda_sync.payload_bytes(Vs, Ks)
