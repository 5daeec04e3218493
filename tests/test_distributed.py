"""SPMD behaviour on 8 forced host devices (subprocess — the main test
process keeps 1 device per the dry-run isolation rule)."""
import textwrap

import pytest

from conftest import run_subprocess

COMMON = """
import jax, numpy as np
from repro.data.synthetic import lda_corpus
from repro.core import trainer
from repro.distributed.partition import DistributedLDA
corpus = lda_corpus(num_docs=48, num_words=96, num_topics=8, avg_doc_len=40, seed=1)
cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8, seed=0)
"""


def test_shard_map_shim_one_step_in_process():
    """Regression for the jax.shard_map import failure: importing
    repro.distributed.partition and running a 1-step 1D iteration through
    ``jax.shard_map`` must work on the installed jax.  Runs in-process on a
    1-device mesh — no subprocess, not slow — so CI catches a broken
    shard_map call immediately."""
    import jax
    import numpy as np

    from repro.core import trainer
    from repro.data.synthetic import lda_corpus
    from repro.distributed.partition import DistributedLDA

    corpus = lda_corpus(num_docs=12, num_words=48, num_topics=4,
                        avg_doc_len=20, seed=2)
    cfg = trainer.LDAConfig(num_topics=4, tile_tokens=16, tiles_per_step=4,
                            seed=0)
    mesh = jax.make_mesh((1,), ("data",))
    dl = DistributedLDA(cfg, mesh, corpus, mode="1d", doc_axes=("data",),
                        word_axes=())
    state = dl.init()
    state, stats = dl.step(state)
    assert np.asarray(state.phi_vk).sum() == corpus.num_tokens
    assert np.isfinite(dl.log_likelihood(state))


@pytest.mark.slow
def test_1d_paper_partition_runs_and_converges():
    out = run_subprocess(COMMON + textwrap.dedent("""
        mesh = jax.make_mesh((8,), ("data",))
        dl = DistributedLDA(cfg, mesh, corpus, mode="1d", doc_axes=("data",), word_axes=())
        state = dl.init()
        ll0 = dl.log_likelihood(state)
        for _ in range(12):
            state, stats = dl.step(state)
        ll1 = dl.log_likelihood(state)
        assert ll1 > ll0 + 0.5, (ll0, ll1)
        phi = np.asarray(state.phi_vk)
        assert phi.sum() == corpus.num_tokens
        print("OK", ll0, ll1)
    """))
    assert "OK" in out


@pytest.mark.slow
def test_2d_partition_equivalent_convergence():
    out = run_subprocess(COMMON + textwrap.dedent("""
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        dl = DistributedLDA(cfg, mesh, corpus, mode="2d", doc_axes=("data",),
                            word_axes=("model",))
        state = dl.init()
        # 16 iters, not 12: at 12 the LL still sits within seed noise of the
        # -4.9 bar (1D with 4 doc shards lands at -4.88 on this seed); by 16
        # every partition reaches ~-4.45, so this asserts convergence rather
        # than seed luck.
        for _ in range(16):
            state, stats = dl.step(state)
        ll = dl.log_likelihood(state)
        assert ll > -4.9, ll
        assert np.asarray(state.phi_vk).sum() == corpus.num_tokens
        print("OK", ll)
    """))
    assert "OK" in out


@pytest.mark.slow
def test_elastic_restore_1d_to_2d_exact():
    """Checkpoint on 8-dev 1D, restore on (4,2) 2D: counts identical."""
    out = run_subprocess(COMMON + textwrap.dedent("""
        import tempfile
        from repro.distributed.checkpoint import CheckpointManager
        mesh1 = jax.make_mesh((8,), ("data",))
        dl1 = DistributedLDA(cfg, mesh1, corpus, mode="1d", doc_axes=("data",), word_axes=())
        state = dl1.init()
        for _ in range(5):
            state, _ = dl1.step(state)
        mesh2 = jax.make_mesh((4, 2), ("data", "model"))
        dl2 = DistributedLDA(cfg, mesh2, corpus, mode="2d", doc_axes=("data",),
                             word_axes=("model",))
        with tempfile.TemporaryDirectory() as td:
            mgr = CheckpointManager(td, async_write=False)
            dl1.save_checkpoint(mgr, state)
            it, z, meta = mgr.latest()
            st2 = dl2.restore(z, it)
        assert (np.asarray(state.phi_sum) == np.asarray(st2.phi_sum)).all()
        ll1 = dl1.log_likelihood(state)
        ll2 = dl2.log_likelihood(st2)
        assert abs(ll1 - ll2) < 2e-3, (ll1, ll2)
        # continue training after the elastic move
        for _ in range(3):
            st2, _ = dl2.step(st2)
        assert dl2.log_likelihood(st2) >= ll2 - 0.05
        print("OK")
    """))
    assert "OK" in out


@pytest.mark.slow
def test_multidevice_matches_singledevice_distribution():
    """1-dev and 8-dev runs reach the same LL plateau (AD-LDA equivalence)."""
    out = run_subprocess(COMMON + textwrap.dedent("""
        from repro.core.corpus import tile_corpus
        res1 = trainer.train(corpus, cfg, 12, eval_every=12)
        mesh = jax.make_mesh((8,), ("data",))
        dl = DistributedLDA(cfg, mesh, corpus, mode="1d", doc_axes=("data",), word_axes=())
        state = dl.init()
        for _ in range(12):
            state, _ = dl.step(state)
        ll8 = dl.log_likelihood(state)
        ll1 = res1.ll_per_token[-1]
        assert abs(ll1 - ll8) < 0.4, (ll1, ll8)
        print("OK", ll1, ll8)
    """))
    assert "OK" in out


@pytest.mark.slow
def test_moe_ep_matches_local():
    """Expert-parallel MoE (all-to-all) == local dense dispatch numerically."""
    out = run_subprocess("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs.archs import smoke
from repro.models import moe as moe_lib
from repro.models.common import ShardingPolicy, NO_SHARDING
cfg = smoke("qwen3-moe-30b-a3b")
cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no drops -> exact match
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
policy = ShardingPolicy(dp=("data",), tp="model", enabled=True, mesh=mesh)
key = jax.random.key(0)
p = moe_lib.init_moe(key, cfg)
x = jax.random.normal(jax.random.fold_in(key, 1), (4, 16, cfg.d_model), jnp.float32)
y_local = moe_lib.moe_ffn_local(p, cfg, x, NO_SHARDING)
y_ep = jax.jit(lambda p, x: moe_lib.moe_ffn_ep(p, cfg, x, policy))(p, x)
np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_ep), atol=2e-2, rtol=2e-2)
print("OK")
""", devices=8)
    assert "OK" in out


@pytest.mark.slow
def test_2d_snapshot_export_canonical():
    """A 2D-trained state exports the *canonical* phi: publish_snapshot on
    DistributedLDA must un-permute the word-sharded rows.  Ground truth is
    phi rebuilt from the canonical z on the host."""
    out = run_subprocess(COMMON + textwrap.dedent("""
        import tempfile
        from repro.distributed.checkpoint import (CheckpointManager,
                                                  gather_canonical_z)
        from repro.serve import load_snapshot
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        dl = DistributedLDA(cfg, mesh, corpus, mode="2d", doc_axes=("data",),
                            word_axes=("model",))
        state = dl.init()
        for _ in range(3):
            state, _ = dl.step(state)
        z = gather_canonical_z(state.z, dl.stacked["token_uid"],
                               corpus.num_tokens)
        expected = np.zeros((corpus.num_words, cfg.num_topics), np.int32)
        np.add.at(expected, (corpus.word_ids, z.astype(np.int64)), 1)
        with tempfile.TemporaryDirectory() as td:
            mgr = CheckpointManager(td)
            path = dl.publish_snapshot(mgr, state)
            snap = load_snapshot(path)
        assert (np.asarray(snap.phi_vk) == expected).all()
        assert np.asarray(snap.phi_vk).sum() == corpus.num_tokens
        assert snap.num_words_total == corpus.num_words
        assert snap.meta["mode"] == "2d"
        # the raw (un-gathered) state phi really is permuted — the old path
        # would have exported a wrong model
        raw = np.asarray(jax.device_get(state.phi_vk))
        assert raw.shape[0] >= corpus.num_words
        assert not (raw[: corpus.num_words] == expected).all()
        print("OK")
    """))
    assert "OK" in out


def test_heavy_word_rows_1d_and_2d():
    """Words at/above the int16 flux bound get int32-sync rows; light words
    do not.  1d tiles the global ids to every shard; 2d maps each heavy
    word to its owning word shard's local row, zero-padded to a common
    width, in doc-major device order."""
    import numpy as np
    from repro.core.corpus import Corpus
    from repro.distributed import partition

    bound = partition.INT16_FLUX_BOUND
    heavy_a, heavy_b = bound + 100, bound       # both heavy (>= bound)
    word_ids = np.concatenate([
        np.full(heavy_a, 3), np.full(heavy_b, 7),
        np.full(bound - 2, 5),                  # bound-1 total (one more
                                                # below): stays light
        np.arange(10),
    ]).astype(np.int32)
    doc_ids = (np.arange(word_ids.size) % 16).astype(np.int32)
    order = np.argsort(doc_ids, kind="stable")
    corpus = Corpus(doc_ids[order], word_ids[order], 16, 12)

    plan_1d = partition.PartitionPlan("1d", ("data",), (), 4, 1)
    rows = partition.heavy_word_rows(corpus, plan_1d)
    assert rows.shape == (4, 2)
    assert (rows == np.array([3, 7])).all()

    shard_of = (np.arange(12) % 2).astype(np.int32)   # 3 -> shard 1, 7 -> 1
    local_id = (np.arange(12) // 2).astype(np.int32)
    plan_2d = partition.PartitionPlan("2d", ("data",), ("model",), 2, 2,
                                      word_shard_of=shard_of,
                                      word_local_id=local_id,
                                      vocab_shard_size=6)
    rows = partition.heavy_word_rows(corpus, plan_2d)
    assert rows.shape == (4, 2)                  # G=4 devices, H=2 padded
    # both heavy words live on word shard 1 (odd ids); device order is
    # doc-major: g = d * n_word + m
    for d in (0, 1):
        assert rows[2 * d + 0].tolist() == [0, 0]          # shard 0: padding
        assert rows[2 * d + 1].tolist() == [1, 3]          # local rows of 3, 7


def test_compressed_sync_heavy_rows_exact_one_device():
    """Regression for the int16 flux wrap: a per-entry delta beyond 2^15
    wraps on the plain compressed path (that wrap is the hazard) and comes
    back exact through the heavy-row int32 correction — observable even on
    a single-device mesh, where psum is identity but the int16 round-trip
    still truncates."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import sync
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    delta = (jnp.zeros((4, 3), jnp.int32)
             .at[1, 2].set(40000).at[2, 0].set(-30000).at[0, 1].set(123))
    heavy = jnp.asarray([1, 2], jnp.int32)

    def run(fn):
        mapped = jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                               check_vma=False)
        return np.asarray(jax.jit(mapped)(delta))

    wrapped = run(lambda d: sync.compressed_sync_phi(d, ("data",)))
    assert wrapped[1, 2] == 40000 - (1 << 16)    # the silent corruption
    assert wrapped[0, 1] == 123                  # light entries were fine

    fixed = run(lambda d: sync.compressed_sync_phi(d, ("data",), heavy))
    assert (fixed == np.asarray(delta)).all()

    # duplicate/padding row ids are harmless (idempotent set)
    padded = jnp.asarray([1, 2, 2, 0], jnp.int32)
    fixed2 = run(lambda d: sync.compressed_sync_phi(d, ("data",), padded))
    assert (fixed2 == np.asarray(delta)).all()


def test_mesh_pallas_matches_sq_one_device_in_process():
    """Fast gate for the mesh-sharded pallas sweep: on a 1-device mesh the
    fused kernel must draw bit-identically to the sq scan through the same
    shard_map plumbing (plans stacked and passed as data).  In-process so a
    broken plan-through-shard_map path fails CI without the slow marker."""
    import dataclasses

    import jax
    import numpy as np

    from repro.core import trainer
    from repro.data.synthetic import lda_corpus
    from repro.distributed.partition import DistributedLDA

    corpus = lda_corpus(num_docs=12, num_words=48, num_topics=4,
                        avg_doc_len=20, seed=2)
    cfg = trainer.LDAConfig(num_topics=4, tile_tokens=16, tiles_per_step=4,
                            micro_chunks=2, seed=0)
    mesh = jax.make_mesh((1,), ("data",))
    states = {}
    for sampler in ("sq", "pallas"):
        c = dataclasses.replace(cfg, sampler=sampler)
        dl = DistributedLDA(c, mesh, corpus, mode="1d", doc_axes=("data",),
                            word_axes=())
        state = dl.init()
        for _ in range(2):
            state, _ = dl.step(state)
        states[sampler] = state
    assert (np.asarray(states["sq"].z)
            == np.asarray(states["pallas"].z)).all()
    assert (np.asarray(states["sq"].phi_vk)
            == np.asarray(states["pallas"].phi_vk)).all()


@pytest.mark.slow
def test_mesh_pallas_matches_sq_1d():
    """Tentpole parity: the fused pallas sweep on an 8-shard 1d mesh draws
    bit-identically to the sharded sq scan under the same key — across z
    dtype (int16/int32) and both work schedules (M=1 single-chunk, M=2
    micro-chunked)."""
    out = run_subprocess(COMMON + textwrap.dedent("""
        import dataclasses, jax.numpy as jnp
        mesh = jax.make_mesh((8,), ("data",))
        for dtype in (jnp.int16, jnp.int32):
            for M in (1, 2):
                states = {}
                for sampler in ("sq", "pallas"):
                    c = dataclasses.replace(cfg, sampler=sampler,
                                            topic_dtype=dtype,
                                            micro_chunks=M)
                    dl = DistributedLDA(c, mesh, corpus, mode="1d",
                                        doc_axes=("data",), word_axes=())
                    state = dl.init()
                    for _ in range(2):
                        state, _ = dl.step(state)
                    states[sampler] = state
                a, b = states["sq"], states["pallas"]
                tag = (dtype.__name__, M)
                assert (np.asarray(a.z) == np.asarray(b.z)).all(), tag
                assert (np.asarray(a.phi_vk) == np.asarray(b.phi_vk)).all(), tag
                assert np.asarray(b.phi_vk).sum() == corpus.num_tokens, tag
        print("OK")
    """))
    assert "OK" in out


@pytest.mark.slow
def test_mesh_pallas_matches_sq_2d_compressed_heavy():
    """2d (4x2) parity with the compressed int16 sync and a *planted* heavy
    word: INT16_FLUX_BOUND patched down to 8 so real corpus words cross it
    and the int32 heavy-row correction is genuinely on the sync path the
    pallas sweep inherits."""
    out = run_subprocess(COMMON + textwrap.dedent("""
        import dataclasses, jax.numpy as jnp
        from repro.distributed import partition
        partition.INT16_FLUX_BOUND = 8        # plant heavy words
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        for comp in (False, True):
            states = {}
            for sampler in ("sq", "pallas"):
                c = dataclasses.replace(cfg, sampler=sampler,
                                        topic_dtype=jnp.int32,
                                        micro_chunks=2, compressed_sync=comp)
                dl = DistributedLDA(c, mesh, corpus, mode="2d",
                                    doc_axes=("data",), word_axes=("model",))
                if comp:
                    assert dl._heavy.shape[1] > 0   # the plant took
                state = dl.init()
                for _ in range(2):
                    state, _ = dl.step(state)
                states[sampler] = state
            a, b = states["sq"], states["pallas"]
            assert (np.asarray(a.z) == np.asarray(b.z)).all(), comp
            assert (np.asarray(a.phi_vk) == np.asarray(b.phi_vk)).all(), comp
        print("OK")
    """))
    assert "OK" in out


@pytest.mark.slow
def test_sync_overlap_matches_serialized():
    """Overlapping the phi_delta all-reduce with the next micro-chunk's
    sampling is a pure schedule change: final (z, phi_vk, phi_sum) must be
    bit-identical to the serialized end-of-iteration sync — for both
    samplers and both sync wire formats (exact int32 and compressed int16
    with planted heavy rows)."""
    out = run_subprocess(COMMON + textwrap.dedent("""
        import dataclasses
        from repro.distributed import partition
        partition.INT16_FLUX_BOUND = 8
        mesh = jax.make_mesh((8,), ("data",))
        for sampler in ("sq", "pallas"):
            for comp in (False, True):
                states = {}
                for overlap in (False, True):
                    c = dataclasses.replace(cfg, sampler=sampler,
                                            micro_chunks=2,
                                            compressed_sync=comp,
                                            sync_overlap=overlap)
                    dl = DistributedLDA(c, mesh, corpus, mode="1d",
                                        doc_axes=("data",), word_axes=())
                    if comp:
                        assert dl._heavy.shape[1] > 0
                    state = dl.init()
                    for _ in range(2):
                        state, _ = dl.step(state)
                    states[overlap] = state
                a, b = states[False], states[True]
                tag = (sampler, comp)
                assert (np.asarray(a.z) == np.asarray(b.z)).all(), tag
                assert (np.asarray(a.phi_vk) == np.asarray(b.phi_vk)).all(), tag
                assert (np.asarray(a.phi_sum) == np.asarray(b.phi_sum)).all(), tag
        print("OK")
    """))
    assert "OK" in out


@pytest.mark.slow
def test_compressed_sync_matches_exact():
    """int16 delta all-reduce == int32 rebuild on small corpora (flux < 2^15)."""
    out = run_subprocess(COMMON + textwrap.dedent("""
        import dataclasses
        mesh = jax.make_mesh((8,), ("data",))
        lls = {}
        for comp in (False, True):
            c = dataclasses.replace(cfg, compressed_sync=comp)
            dl = DistributedLDA(c, mesh, corpus, mode="1d",
                                doc_axes=("data",), word_axes=())
            state = dl.init()
            for _ in range(6):
                state, _ = dl.step(state)
            phi = np.asarray(state.phi_vk)
            assert phi.sum() == corpus.num_tokens
            lls[comp] = (dl.log_likelihood(state), phi)
        # identical RNG stream -> identical states when compression is exact
        assert (lls[False][1] == lls[True][1]).all()
        print("OK", lls[False][0])
    """))
    assert "OK" in out


@pytest.mark.slow
def test_mesh_corpus_is_committed_where_the_step_reads_it():
    """The stacked corpus and the heavy rows are committed
    ``NamedSharding(mesh, P(lead))`` arrays, each device holding exactly
    its own shard's rows, and two iterations draw exactly what the same
    step draws from the corpus stacked on the default device (the
    construction before the corpus was placed on the mesh)."""
    out = run_subprocess(COMMON + textwrap.dedent("""
        import dataclasses, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed.partition import build_shards, stack_shards
        cases = (("1d", (4,), ("data",), {}, "pallas"),
                 ("2d", (2, 2), ("data", "model"),
                  dict(doc_axes=("data",), word_axes=("model",)), "sq"))
        for mode, shape, axes, kw, sampler in cases:
            mesh = jax.make_mesh(shape, axes)
            c = dataclasses.replace(cfg, sampler=sampler)
            dl = DistributedLDA(c, mesh, corpus, mode=mode, **kw)
            lead = dl.plan.doc_axes + dl.plan.word_axes
            want = NamedSharding(mesh, P(lead))
            shards, _, full = build_shards(corpus, dl.plan.num_doc_shards,
                                           dl.plan.num_word_shards, mode,
                                           c.tile_tokens)
            host = stack_shards(shards, full)
            G = len(shards)
            for k, v in dict(dl.stacked, heavy=dl._heavy).items():
                assert v.committed, (mode, k)
                assert v.sharding.is_equivalent_to(want, v.ndim), (mode, k)
                held = []
                for s in v.addressable_shards:
                    g = s.index[0].start or 0
                    held.append(g)
                    assert s.data.shape[0] == 1, (mode, k)
                    if k != "heavy":
                        np.testing.assert_array_equal(
                            np.asarray(s.data), host[k][g:g + 1])
                assert sorted(held) == list(range(G)), (mode, k, held)
            old = {k: jnp.stack([jnp.asarray(x) for x in v])
                   for k, v in host.items()}
            assert not old["token_uid"].committed
            heavy_old = jnp.asarray(np.asarray(dl._heavy))
            key = jax.random.key(7)
            a = b = dl.init(3)
            for _ in range(2):
                a, sa = dl.step(a, key)
                with mesh:
                    b, sb = dl._step_fn(old, heavy_old, b, key)
            for f in ("z", "phi_vk", "phi_sum"):
                assert (np.asarray(getattr(a, f))
                        == np.asarray(getattr(b, f))).all(), (mode, f)
            assert float(sa.sparse_frac) == float(sb.sparse_frac), mode
        print("OK")
    """), devices=4)
    assert "OK" in out


@pytest.mark.parametrize("mode", ["1d", "2d"])
def test_one_pass_build_matches_the_two_pass_build(mode):
    """``build_shards`` tiles each shard once, its padding taken from the
    shards' word counts; its shards equal, field for field, those of
    tiling every shard twice (a pass to find the largest tile count, then
    a padded pass)."""
    import numpy as np

    from repro.core.corpus import partition_by_document, tile_shard
    from repro.data.synthetic import lda_corpus
    from repro.distributed import partition

    corpus = lda_corpus(num_docs=48, num_words=96, num_topics=8,
                        avg_doc_len=40, seed=1)
    t, (n_doc, n_word) = 16, ((4, 1) if mode == "1d" else (2, 2))
    shards, plan, full = partition.build_shards(corpus, n_doc, n_word,
                                                mode, t)
    subs = []
    for pd in partition_by_document(corpus, n_doc):
        sel = np.isin(corpus.doc_ids, pd)
        if mode == "1d":
            subs.append((*partition._subset(corpus, sel, None,
                                            corpus.num_words), pd))
            continue
        shard_of, local_id, v_local = partition.partition_vocabulary(
            corpus, n_word)
        for m in range(n_word):
            subs.append((*partition._subset(
                corpus, sel & (shard_of[corpus.word_ids] == m), local_id,
                v_local), pd))
    V = corpus.num_words
    pre = [tile_shard(sub, pd, t, token_uid=uid, num_words_total=V)
           for sub, uid, pd in subs]
    n_max = max(s.tile_word.shape[0] for s in pre)
    two = [tile_shard(sub, pd, t, n_max, token_uid=uid, num_words_total=V)
           for sub, uid, pd in subs]
    assert len(shards) == len(two) == n_doc * n_word
    for a, b in zip(shards, two):
        for f in ("tile_word", "token_doc", "token_mask", "tile_first",
                  "doc_length", "doc_global", "token_uid"):
            x, y = getattr(a, f), np.asarray(getattr(b, f))
            assert x.dtype == y.dtype and x.shape == y.shape, f
            np.testing.assert_array_equal(x, y, err_msg=f)
        assert (a.num_tokens, a.num_words, a.num_docs_local,
                a.num_words_total) == (b.num_tokens, b.num_words,
                                       b.num_docs_local, b.num_words_total)
    for x, (_, _, pd) in zip(full, subs):
        np.testing.assert_array_equal(x, corpus.doc_lengths()[pd])
    assert plan.mode == mode and plan.num_doc_shards == n_doc
