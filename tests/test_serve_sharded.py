"""V-sharded serving (ISSUE 3 tentpole + ISSUE 4's all2all comm strategy):
snapshot layout roundtrip, the shard_map'd fold-in's draw-identity with the
single-device path under BOTH gather strategies (full psum and request-side
all-to-all token routing), hot-swap across layouts, and sharded publish
from trainers.

In-process tests shard over ``min(local_device_count, 4)`` devices — 1 in
the default suite, 8 under the CI distributed job's
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` step — so the mesh
path is exercised for real on CPU.  The ``slow`` subprocess tests always
force 8 host devices (same pattern as test_distributed)."""
import os
import textwrap

import numpy as np
import jax
import pytest

from conftest import run_subprocess
from test_foldin_kernel import planted_case

from repro.serve import (EngineConfig, HotSwapModel, InferConfig,
                         LDAServeEngine, ModelSnapshot,
                         assemble_sharded_snapshot, load_any_snapshot,
                         load_sharded_snapshot, save_sharded_snapshot,
                         shard_snapshot)
from repro.serve.infer import fold_in, fold_in_config
from repro.serve.snapshot import plan_contiguous_shards

N_SHARDS = min(jax.local_device_count(), 4)


def _run_dense(snap, tokens, mask, key, cfg: InferConfig):
    return fold_in(snap.phi_vk, snap.phi_sum, tokens, mask, key,
                   snap.alpha, snap.beta,
                   num_words_total=snap.num_words_total,
                   burn_in=cfg.burn_in, samples=cfg.samples,
                   top_k=cfg.top_k, impl=cfg.impl)


class TestShardedLayout:
    def test_contiguous_plan_is_bijective(self):
        shard_of, local_id, rows = plan_contiguous_shards(100, 8)
        assert rows == 13
        assert shard_of.min() == 0 and shard_of.max() == 7
        # (shard, local) pairs are unique -> scatter/gather is lossless
        flat = shard_of.astype(np.int64) * rows + local_id
        assert len(np.unique(flat)) == 100

    def test_save_load_assemble_roundtrip(self, tmp_path):
        snap, _, _, _ = planted_case(8, num_docs=1, doc_len=4)
        snap = ModelSnapshot(
            phi_vk=snap.phi_vk, phi_sum=snap.phi_sum, alpha=0.3, beta=0.05,
            num_words_total=snap.num_words_total, meta={"iteration": 7},
            vocab=tuple(f"w{v}" for v in range(snap.num_words)))
        p = save_sharded_snapshot(str(tmp_path / "m.sharded"), snap,
                                  num_shards=3)
        # host-side assemble needs no mesh: verifies the on-disk layout
        back = assemble_sharded_snapshot(p)
        np.testing.assert_array_equal(np.asarray(back.phi_vk),
                                      np.asarray(snap.phi_vk))
        np.testing.assert_array_equal(np.asarray(back.phi_sum),
                                      np.asarray(snap.phi_sum))
        assert back.alpha == 0.3 and back.beta == 0.05
        assert back.meta["iteration"] == 7
        assert back.vocab == snap.vocab

    def test_load_rejects_too_few_devices(self, tmp_path):
        snap, _, _, _ = planted_case(8, num_docs=1, doc_len=4)
        p = save_sharded_snapshot(str(tmp_path / "m.sharded"), snap,
                                  num_shards=jax.local_device_count() + 1)
        with pytest.raises(ValueError, match="devices"):
            load_sharded_snapshot(p)

    def test_load_any_dispatches_on_layout(self, tmp_path):
        from repro.serve import save_snapshot

        snap, _, _, _ = planted_case(8, num_docs=1, doc_len=4)
        dense_p = save_snapshot(str(tmp_path / "m.npz"), snap)
        shard_p = save_sharded_snapshot(str(tmp_path / "m.sharded"), snap,
                                        num_shards=N_SHARDS)
        assert isinstance(load_any_snapshot(dense_p), ModelSnapshot)
        sh = load_any_snapshot(shard_p)
        assert sh.num_shards == N_SHARDS
        # --shards: a dense file re-shards at load
        resh = load_any_snapshot(dense_p, shards=max(N_SHARDS, 1))
        if N_SHARDS > 1:
            assert resh.num_shards == N_SHARDS

    def test_publish_sharded_from_training_state(self, tmp_path, tiny_corpus):
        from repro.core import trainer
        from repro.distributed.checkpoint import CheckpointManager

        cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8)
        res = trainer.train(tiny_corpus, cfg, 2, eval_every=2)
        mgr = CheckpointManager(str(tmp_path), keep=1)
        p = mgr.publish_snapshot(res.state, cfg.resolved_alpha(), cfg.beta,
                                 num_words_total=tiny_corpus.num_words,
                                 shards=2)
        assert p.endswith(".sharded") and mgr.latest_snapshot_path() == p
        back = assemble_sharded_snapshot(p)
        np.testing.assert_array_equal(np.asarray(back.phi_vk),
                                      np.asarray(res.state.phi_vk))
        # keep-N pruning treats sharded dirs like dense files
        p2 = mgr.publish_snapshot(res.state, cfg.resolved_alpha(), cfg.beta,
                                  num_words_total=tiny_corpus.num_words)
        assert mgr.latest_snapshot_path() == p2
        assert not os.path.exists(p)


class TestShardedFoldIn:
    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_draw_identical_to_dense(self, impl):
        """The acceptance bar: a V-sharded snapshot serves draws bit-identical
        to the same model unsharded, given the same key."""
        snap, tokens, mask, _ = planted_case(8, num_docs=6, doc_len=24,
                                             seed=3, length=32)
        cfg = InferConfig(burn_in=4, samples=2, impl=impl)
        key = jax.random.key(11)
        dense = _run_dense(snap, tokens, mask, key, cfg)
        sharded = fold_in_config(shard_snapshot(snap, N_SHARDS), tokens,
                                 mask, key, cfg)
        np.testing.assert_array_equal(np.asarray(dense.theta),
                                      np.asarray(sharded.theta))
        np.testing.assert_array_equal(np.asarray(dense.top_topics),
                                      np.asarray(sharded.top_topics))
        np.testing.assert_array_equal(np.asarray(dense.sparse_frac),
                                      np.asarray(sharded.sparse_frac))

    def test_engine_sharded_draws_match_dense_engine(self):
        """Same seed, same docs, one batch: the sharded engine's served theta
        equals the dense engine's bit for bit, with one H2D per batch."""
        snap, _, _, _ = planted_case(8, num_docs=1, doc_len=8)

        def mk(s):
            return LDAServeEngine(HotSwapModel(s), EngineConfig(
                max_batch=4, max_delay_ms=150.0, length_buckets=(32,),
                infer=InferConfig(burn_in=3, samples=2)), seed=5)

        docs = [np.arange(k * 8, k * 8 + 8, dtype=np.int32) for k in (0, 1, 2)]
        e_dense, e_shard = mk(snap), mk(shard_snapshot(snap, N_SHARDS))
        try:
            for r1, r2 in zip(e_dense.infer_many(docs),
                              e_shard.infer_many(docs)):
                np.testing.assert_array_equal(r1["theta"], r2["theta"])
            s = e_shard.stats()
            assert s["h2d_transfers"] == s["batches"]
        finally:
            e_dense.stop()
            e_shard.stop()

    def test_hot_swap_between_sharded_and_dense(self):
        """Dense -> sharded -> dense publishes on a live engine: versions
        bump, answers stay correct, nothing restarts."""
        snap, _, _, _ = planted_case(8, num_docs=1, doc_len=8)
        eng = LDAServeEngine(HotSwapModel(snap), EngineConfig(
            max_batch=2, max_delay_ms=20.0, length_buckets=(32,),
            infer=InferConfig(burn_in=3, samples=2)))
        try:
            doc = np.arange(0, 8, dtype=np.int32)        # topic-0 words
            r1 = eng.infer(doc)
            assert r1["model_version"] == 1
            assert int(r1["theta"].argmax()) == 0
            eng.model.publish(shard_snapshot(snap, N_SHARDS))
            r2 = eng.infer(doc)
            assert r2["model_version"] == 2
            assert int(r2["theta"].argmax()) == 0
            eng.model.publish(snap)
            r3 = eng.infer(doc)
            assert r3["model_version"] == 3
            assert int(r3["theta"].argmax()) == 0
        finally:
            eng.stop()

    def test_sharded_heldout_perplexity(self):
        from repro.serve import heldout_perplexity

        snap, _, _, _ = planted_case(8, num_docs=1, doc_len=8)
        rng = np.random.default_rng(0)
        docs = [rng.integers(0, snap.num_words, 30).astype(np.int32)
                for _ in range(6)]
        dense = heldout_perplexity(snap, docs, InferConfig(burn_in=3,
                                                           samples=2), seed=0)
        sharded = heldout_perplexity(shard_snapshot(snap, N_SHARDS), docs,
                                     InferConfig(burn_in=3, samples=2),
                                     seed=0)
        assert sharded.perplexity == pytest.approx(dense.perplexity)


class TestAllToAllFoldIn:
    """Request-side all-to-all comm strategy (ISSUE 4 tentpole): token ids
    routed to the owning shard, gathered rows routed back, sweeps per doc
    slice — and still bit-identical to the psum and dense paths."""

    @pytest.mark.parametrize("impl", ["xla", "pallas"])
    def test_draw_identical_to_psum_and_dense(self, impl):
        """The acceptance bar: same key -> same draws under dense gather,
        sharded psum, and sharded all2all, for every impl.  Six docs over
        up-to-4 shards exercises the non-divisible (overlapping-slice) case,
        short docs exercise padding (rows of padded slots are zeros under
        all2all but psum'd under psum — outputs must not care)."""
        snap, tokens, mask, _ = planted_case(8, num_docs=6, doc_len=24,
                                             seed=3, length=32)
        assert not mask.all()
        key = jax.random.key(11)
        cfg = lambda comm: InferConfig(burn_in=4, samples=2, impl=impl,
                                       comm=comm)
        dense = _run_dense(snap, tokens, mask, key, cfg("psum"))
        sh = shard_snapshot(snap, N_SHARDS)
        psum = fold_in_config(sh, tokens, mask, key, cfg("psum"))
        a2a = fold_in_config(sh, tokens, mask, key, cfg("all2all"))
        for other in (psum, a2a):
            np.testing.assert_array_equal(np.asarray(dense.theta),
                                          np.asarray(other.theta))
            np.testing.assert_array_equal(np.asarray(dense.top_topics),
                                          np.asarray(other.top_topics))
            np.testing.assert_array_equal(np.asarray(dense.top_weights),
                                          np.asarray(other.top_weights))
            np.testing.assert_array_equal(np.asarray(dense.sparse_frac),
                                          np.asarray(other.sparse_frac))
            # float reduction order differs across slices — ulp-level only
            np.testing.assert_allclose(np.asarray(dense.mean_s_over_sq),
                                       np.asarray(other.mean_s_over_sq),
                                       rtol=1e-6)

    def test_auto_comm_defers_to_snapshot_tag(self):
        from repro.serve.infer import resolve_comm

        snap, tokens, mask, _ = planted_case(8, num_docs=3, doc_len=8)
        sh = shard_snapshot(snap, N_SHARDS, comm="all2all")
        assert resolve_comm(sh, InferConfig()) == "all2all"
        assert resolve_comm(sh, InferConfig(comm="psum")) == "psum"
        with pytest.raises(ValueError, match="comm"):
            resolve_comm(sh, InferConfig(comm="carrier-pigeon"))
        # and the auto-resolved path actually serves correct draws
        key = jax.random.key(5)
        dense = _run_dense(snap, tokens, mask, key, InferConfig(burn_in=3,
                                                                samples=2))
        auto = fold_in_config(sh, tokens, mask, key,
                              InferConfig(burn_in=3, samples=2))
        np.testing.assert_array_equal(np.asarray(dense.theta),
                                      np.asarray(auto.theta))

    def test_sharded_save_load_keeps_comm_tag(self, tmp_path):
        snap, _, _, _ = planted_case(8, num_docs=1, doc_len=4)
        sh = shard_snapshot(snap, N_SHARDS, comm="all2all")
        p = save_sharded_snapshot(str(tmp_path / "m.sharded"), sh)
        assert load_sharded_snapshot(p).comm == "all2all"
        assert load_sharded_snapshot(p, comm="psum").comm == "psum"
        assert load_any_snapshot(p).comm == "all2all"

    def test_routing_plan_capacity_and_bytes(self):
        from repro.distributed.partition import plan_token_routing

        rng = np.random.default_rng(0)
        V, B, L, K, S = 97, 6, 32, 16, 4
        shard_of = rng.integers(0, S, V).astype(np.int32)
        tokens = rng.integers(0, V, (B, L)).astype(np.int32)
        mask = rng.random((B, L)) < 0.6
        plan = plan_token_routing(shard_of, tokens, mask, S, K)
        # capacity: a power of two that genuinely bounds every bucket
        assert plan.capacity & (plan.capacity - 1) == 0
        starts = np.minimum(np.arange(S) * plan.docs_per_shard,
                            B - plan.docs_per_shard)
        for s in range(S):
            sl = slice(starts[s], starts[s] + plan.docs_per_shard)
            loads = np.bincount(shard_of[tokens[sl][mask[sl]]], minlength=S)
            assert loads.max() <= plan.capacity
        # the whole point: routed volume beats the dense psum
        assert 0 < plan.a2a_bytes < plan.psum_bytes
        # worst case stays exact: every token the same word
        worst = plan_token_routing(shard_of, np.zeros((B, L), np.int32),
                                   np.ones((B, L), bool), S, K)
        assert worst.capacity <= worst.docs_per_shard * L

    def test_route_buckets_is_lossless(self):
        """Every real token lands in exactly one (owner, slot) and its source
        position survives the round trip; padding routes nowhere."""
        from repro.distributed.partition import route_buckets

        rng = np.random.default_rng(1)
        S, T, C = 4, 64, 32
        owner = rng.integers(0, S + 1, T).astype(np.int32)   # S == padding
        payload = np.arange(T, dtype=np.int32) + 1000
        send, src = jax.jit(route_buckets, static_argnums=(2, 3))(
            owner, payload, S, C)
        send, src = np.asarray(send), np.asarray(src)
        real = np.nonzero(owner < S)[0]
        placed = src[src < T]
        assert sorted(placed.tolist()) == sorted(real.tolist())
        for o in range(S):
            slots = np.nonzero(src[o] < T)[0]
            assert (owner[src[o, slots]] == o).all()
            assert (send[o, slots] == payload[src[o, slots]]).all()

    def test_doc_slices_cover_every_batch_size(self):
        """Slice bounds + dedup map stay consistent for any (B, S), including
        B < S and non-divisible overlaps."""
        from repro.distributed.partition import (doc_slice_bounds,
                                                 doc_slice_owner)

        for B in range(1, 11):
            for S in range(1, 7):
                starts, per = doc_slice_bounds(B, S)
                assert starts.shape == (S,) and per == -(-B // S)
                assert (starts >= 0).all() and (starts + per <= B).all()
                owner, row = doc_slice_owner(B, S)
                assert ((0 <= row) & (row < per)).all()
                np.testing.assert_array_equal(starts[owner] + row,
                                              np.arange(B))

    def test_engine_all2all_matches_dense_engine(self):
        """Same seed, same docs: the all2all engine's served theta equals the
        dense engine's bit for bit, one H2D per batch, and the comm-bytes
        meter runs whenever shards actually exchange data."""
        snap, _, _, _ = planted_case(8, num_docs=1, doc_len=8)

        def mk(s, comm):
            return LDAServeEngine(HotSwapModel(s), EngineConfig(
                max_batch=4, max_delay_ms=150.0, length_buckets=(32,),
                infer=InferConfig(burn_in=3, samples=2, comm=comm)), seed=5)

        docs = [np.arange(k * 8, k * 8 + 8, dtype=np.int32) for k in (0, 1, 2)]
        e_dense = mk(snap, "auto")
        e_a2a = mk(shard_snapshot(snap, N_SHARDS), "all2all")
        try:
            for r1, r2 in zip(e_dense.infer_many(docs),
                              e_a2a.infer_many(docs)):
                np.testing.assert_array_equal(r1["theta"], r2["theta"])
            s = e_a2a.stats()
            assert s["h2d_transfers"] == s["batches"]
            assert (s["comm_bytes_moved"] > 0) == (N_SHARDS > 1)
            assert e_dense.stats()["comm_bytes_moved"] == 0
        finally:
            e_dense.stop()
            e_a2a.stop()


@pytest.mark.slow
def test_all2all_parity_on_8_devices():
    """The real mesh: phi over 8 word shards on 8 forced host devices, the
    all2all strategy draw-identical to psum and dense for every impl, served
    through the engine, with the measured bytes reduction >1x."""
    out = run_subprocess(textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.serve import (EngineConfig, HotSwapModel, InferConfig,
                                 LDAServeEngine, ModelSnapshot, shard_snapshot)
        from repro.serve.infer import (fold_in, fold_in_config, pack_docs,
                                       routing_plan)
        assert jax.local_device_count() == 8
        V, K = 160, 16
        rng = np.random.default_rng(0)
        phi = rng.integers(0, 50, (V, K)).astype(np.int32)
        snap = ModelSnapshot(phi_vk=jnp.asarray(phi),
                             phi_sum=jnp.asarray(phi.sum(0)),
                             alpha=0.1, beta=0.01, num_words_total=V)
        docs = [rng.integers(0, V, n).astype(np.int32)
                for n in (10, 17, 5, 30, 32, 2)]
        tokens, mask = pack_docs(docs, 32)
        key = jax.random.key(7)
        sh = shard_snapshot(snap, 8)
        plan = routing_plan(sh, tokens, mask)
        assert plan.psum_bytes / plan.a2a_bytes > 1.0, plan
        for impl in ("xla", "pallas"):
            dense = fold_in(snap.phi_vk, snap.phi_sum, tokens, mask, key,
                            snap.alpha, snap.beta, num_words_total=V,
                            burn_in=4, samples=2, impl=impl)
            for comm in ("psum", "all2all"):
                got = fold_in_config(sh, tokens, mask, key,
                                     InferConfig(burn_in=4, samples=2,
                                                 impl=impl, comm=comm))
                np.testing.assert_array_equal(np.asarray(dense.theta),
                                              np.asarray(got.theta))
                np.testing.assert_array_equal(np.asarray(dense.sparse_frac),
                                              np.asarray(got.sparse_frac))
        ecfg = lambda comm: EngineConfig(max_batch=8, max_delay_ms=150.0,
                                         length_buckets=(32,),
                                         infer=InferConfig(burn_in=3,
                                                           samples=2,
                                                           comm=comm))
        e1 = LDAServeEngine(HotSwapModel(snap), ecfg("auto"), seed=5)
        e2 = LDAServeEngine(HotSwapModel(sh), ecfg("all2all"), seed=5)
        for r1, r2 in zip(e1.infer_many(docs), e2.infer_many(docs)):
            np.testing.assert_array_equal(r1["theta"], r2["theta"])
        s = e2.stats()
        assert s["h2d_transfers"] == s["batches"]
        assert s["comm_bytes_moved"] > 0
        e1.stop(); e2.stop()
        print("OK")
    """))
    assert "OK" in out


@pytest.mark.slow
def test_sharded_parity_on_8_devices():
    """The real mesh: phi over 4 word shards on 8 forced host devices, every
    impl draw-identical to the dense path, served through the engine."""
    out = run_subprocess(textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.serve import (EngineConfig, HotSwapModel, InferConfig,
                                 LDAServeEngine, ModelSnapshot, shard_snapshot)
        from repro.serve.infer import fold_in, fold_in_config, pack_docs
        assert jax.local_device_count() == 8
        V, K = 100, 16
        rng = np.random.default_rng(0)
        phi = rng.integers(0, 50, (V, K)).astype(np.int32)
        snap = ModelSnapshot(phi_vk=jnp.asarray(phi),
                             phi_sum=jnp.asarray(phi.sum(0)),
                             alpha=0.1, beta=0.01, num_words_total=V)
        docs = [rng.integers(0, V, n).astype(np.int32) for n in (10, 17, 5, 30)]
        tokens, mask = pack_docs(docs, 32)
        key = jax.random.key(7)
        sh = shard_snapshot(snap, 4)
        for impl in ("xla", "pallas"):
            cfg = InferConfig(burn_in=4, samples=2, impl=impl)
            dense = fold_in(snap.phi_vk, snap.phi_sum, tokens, mask, key,
                            snap.alpha, snap.beta, num_words_total=V,
                            burn_in=4, samples=2, impl=impl)
            sharded = fold_in_config(sh, tokens, mask, key, cfg)
            np.testing.assert_array_equal(np.asarray(dense.theta),
                                          np.asarray(sharded.theta))
        ecfg = EngineConfig(max_batch=4, max_delay_ms=150.0,
                            length_buckets=(32,),
                            infer=InferConfig(burn_in=3, samples=2))
        e1 = LDAServeEngine(HotSwapModel(snap), ecfg, seed=5)
        e2 = LDAServeEngine(HotSwapModel(sh), ecfg, seed=5)
        for r1, r2 in zip(e1.infer_many(docs), e2.infer_many(docs)):
            np.testing.assert_array_equal(r1["theta"], r2["theta"])
        s = e2.stats()
        assert s["h2d_transfers"] == s["batches"]
        e1.stop(); e2.stop()
        print("OK")
    """))
    assert "OK" in out


@pytest.mark.slow
def test_2d_trainer_publishes_sharded_directly():
    """A 2D-trained state publishes the V-sharded layout from its per-device
    word blocks (LPT maps, no full-phi gather) and the result both assembles
    to the canonical phi and serves draw-identically to the dense path."""
    out = run_subprocess(textwrap.dedent("""
        import jax, numpy as np, tempfile, os
        from repro.data.synthetic import lda_corpus
        from repro.core import trainer
        from repro.distributed.partition import DistributedLDA
        from repro.distributed.checkpoint import (CheckpointManager,
                                                  gather_canonical_z)
        from repro.serve import assemble_sharded_snapshot, load_any_snapshot
        from repro.serve.infer import fold_in, fold_in_config, InferConfig
        from repro.serve import pack_docs
        corpus = lda_corpus(num_docs=48, num_words=96, num_topics=8,
                            avg_doc_len=40, seed=1)
        cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32,
                                tiles_per_step=8, seed=0)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        dl = DistributedLDA(cfg, mesh, corpus, mode="2d",
                            doc_axes=("data",), word_axes=("model",))
        state = dl.init()
        for _ in range(3):
            state, _ = dl.step(state)
        z = gather_canonical_z(state.z, dl.stacked["token_uid"],
                               corpus.num_tokens)
        expected = np.zeros((corpus.num_words, cfg.num_topics), np.int32)
        np.add.at(expected, (corpus.word_ids, z.astype(np.int64)), 1)
        with tempfile.TemporaryDirectory() as td:
            mgr = CheckpointManager(td)
            path = dl.publish_snapshot(mgr, state, shards=2)
            assert path.endswith(".sharded")
            snap = assemble_sharded_snapshot(path)
            assert (np.asarray(snap.phi_vk) == expected).all()
            assert snap.meta["mode"] == "2d"
            assert snap.meta["layout"] == "lpt"
            sh = load_any_snapshot(path)
            rng = np.random.default_rng(0)
            docs = [rng.integers(0, corpus.num_words, 20).astype(np.int32)
                    for _ in range(4)]
            tokens, mask = pack_docs(docs, 32)
            key = jax.random.key(3)
            r_sh = fold_in_config(sh, tokens, mask, key,
                                  InferConfig(burn_in=4, samples=2))
            r_d = fold_in(snap.phi_vk, snap.phi_sum, tokens, mask, key,
                          snap.alpha, snap.beta,
                          num_words_total=snap.num_words_total,
                          burn_in=4, samples=2)
            np.testing.assert_array_equal(np.asarray(r_sh.theta),
                                          np.asarray(r_d.theta))
        print("OK")
    """))
    assert "OK" in out
