"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, bit-exactness.

Kernels run in interpret mode (CPU container); the contract tested here —
identical draws/counts given identical uniforms — is the same one the TPU
build must satisfy.
"""
import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import updates
from repro.core.corpus import ell_capacity, tile_corpus
from repro.data.synthetic import lda_corpus, zipf_corpus
from repro.kernels.lda_sample import ops as sample_ops
from repro.kernels.phi_update import ops as phi_ops


def setup_case(K, tile_tokens, num_docs=24, num_words=48, seed=0,
               topic_dtype=jnp.int16):
    corpus = lda_corpus(num_docs=num_docs, num_words=num_words, num_topics=4,
                        avg_doc_len=30, seed=seed)
    shard = tile_corpus(corpus, 1, tile_tokens)[0]
    n, t = shard.token_doc.shape
    key = jax.random.key(seed)
    z = jax.random.randint(key, (n, t), 0, K, jnp.int32).astype(topic_dtype)
    phi = updates.phi_from_z(z, shard.tile_word, shard.token_mask,
                             corpus.num_words, K)
    theta = updates.theta_from_z(z, shard.token_doc, shard.token_mask,
                                 shard.num_docs_local, K)
    P = ell_capacity(corpus, K)
    cnts, tpcs, _ = updates.theta_to_ell(theta, P)
    return corpus, shard, z, phi, phi.sum(0), cnts, tpcs, key


@pytest.mark.parametrize("K", [128, 256, 512])     # 1, 2, 4 search blocks
@pytest.mark.parametrize("tile_tokens", [16, 64])
def test_lda_sample_kernel_matches_ref(K, tile_tokens):
    corpus, shard, z, phi, phi_sum, cnts, tpcs, key = setup_case(K, tile_tokens)
    kw = dict(alpha=50.0 / K, beta=0.01, num_words_total=corpus.num_words)
    zk, sk = sample_ops.lda_sample(shard.tile_word, shard.token_doc,
                                   shard.token_mask, z, phi, phi_sum,
                                   cnts, tpcs, key, impl="pallas", interpret=True, **kw)
    zr, sr = sample_ops.lda_sample(shard.tile_word, shard.token_doc,
                                   shard.token_mask, z, phi, phi_sum,
                                   cnts, tpcs, key, impl="ref", interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(zk), np.asarray(zr))
    assert abs(float(sk.sparse_frac) - float(sr.sparse_frac)) < 1e-6
    assert abs(float(sk.mean_s_over_sq) - float(sr.mean_s_over_sq)) < 1e-6


@pytest.mark.parametrize("K", [96, 192])  # non-128-multiple -> fallback block
def test_lda_sample_odd_K(K):
    corpus, shard, z, phi, phi_sum, cnts, tpcs, key = setup_case(K, 32)
    kw = dict(alpha=50.0 / K, beta=0.01, num_words_total=corpus.num_words)
    zk, _ = sample_ops.lda_sample(shard.tile_word, shard.token_doc,
                                  shard.token_mask, z, phi, phi_sum,
                                  cnts, tpcs, key, impl="pallas", interpret=True, **kw)
    zr, _ = sample_ops.lda_sample(shard.tile_word, shard.token_doc,
                                  shard.token_mask, z, phi, phi_sum,
                                  cnts, tpcs, key, impl="ref", interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(zk), np.asarray(zr))


@pytest.mark.parametrize("topic_dtype", [jnp.int16, jnp.int32])
def test_lda_sample_dtypes(topic_dtype):
    corpus, shard, z, phi, phi_sum, cnts, tpcs, key = setup_case(
        128, 32, topic_dtype=topic_dtype)
    kw = dict(alpha=0.5, beta=0.01, num_words_total=corpus.num_words)
    zk, _ = sample_ops.lda_sample(shard.tile_word, shard.token_doc,
                                  shard.token_mask, z, phi, phi_sum,
                                  cnts, tpcs, key, impl="pallas", interpret=True, **kw)
    assert zk.dtype == topic_dtype
    assert int(zk.max()) < 128 and int(zk.min()) >= 0


@pytest.mark.parametrize("tiles_per_step", [1, 8, 64])
def test_lda_sample_chunk_width_invariant(tiles_per_step):
    """The chunk width of the XLA sweep never changes the draws (per-tile
    uniforms), so the one-tile-per-step kernel matches every width."""
    from repro.core import sampler as core
    corpus, shard, z, phi, phi_sum, cnts, tpcs, key = setup_case(128, 16)
    kw = dict(alpha=0.4, beta=0.01, num_words_total=corpus.num_words)
    z1, _ = sample_ops.lda_sample(shard.tile_word, shard.token_doc,
                                  shard.token_mask, z, phi, phi_sum, cnts,
                                  tpcs, key, impl="pallas", interpret=True,
                                  **kw)
    zs, _ = core.sample_sweep(phi, phi_sum, shard.tile_word, shard.token_doc,
                              shard.token_mask, z, cnts, tpcs, key,
                              tiles_per_step=tiles_per_step, **kw)
    np.testing.assert_array_equal(np.asarray(z1), np.asarray(zs))


def test_lda_sample_matches_core_sampler():
    """Kernel == repro.core.sampler given the same uniforms (C4/C5/C7)."""
    from repro.core import sampler as core
    corpus, shard, z, phi, phi_sum, cnts, tpcs, key = setup_case(256, 32)
    kw = dict(alpha=0.2, beta=0.01, num_words_total=corpus.num_words)
    n, t = z.shape
    uni = core.draw_sweep_uniforms(key, n, t)   # the sweep's shared contract
    zc = jnp.stack([
        core.sample_one_tile(phi[shard.tile_word[i]], phi_sum,
                             shard.token_doc[i], shard.token_mask[i],
                             z[i].astype(jnp.int32), cnts, tpcs, uni[i], **kw)[0]
        for i in range(n)])
    zk, _ = sample_ops.lda_sample(shard.tile_word, shard.token_doc,
                                  shard.token_mask, z, phi, phi_sum,
                                  cnts, tpcs, key, impl="pallas", interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(zc), np.asarray(zk))


def heavy_tail_case(K, classes, tile_tokens=64, seed=0):
    """Documents in classes of ``(docs, length)``, each class over words of
    its own, so the word tiles of short documents sample narrow row blocks
    and those of long ones wide blocks.  The ELL width is
    ``ell_capacity``: min(K, the longest document) in whole vregs."""
    from repro.core.corpus import Corpus
    rng = np.random.default_rng(seed)
    docs, words, d0 = [], [], 0
    for c, (n_docs, length) in enumerate(classes):
        docs.append(np.repeat(np.arange(d0, d0 + n_docs), length))
        words.append(rng.integers(8 * c, 8 * c + 8, n_docs * length))
        d0 += n_docs
    corpus = Corpus(np.concatenate(docs).astype(np.int32),
                    np.concatenate(words).astype(np.int32), d0,
                    8 * len(classes))
    shard = tile_corpus(corpus, 1, tile_tokens)[0]
    n, t = shard.token_doc.shape
    key = jax.random.key(seed)
    z = jax.random.randint(key, (n, t), 0, K, jnp.int32).astype(jnp.int16)
    phi = updates.phi_from_z(z, shard.tile_word, shard.token_mask,
                             corpus.num_words, K)
    theta = updates.theta_from_z(z, shard.token_doc, shard.token_mask,
                                 shard.num_docs_local, K)
    cnts, tpcs, _ = updates.theta_to_ell(theta, ell_capacity(corpus, K))
    return corpus, shard, z, phi, phi.sum(0), cnts, tpcs, key


def expected_widths(token_doc, token_mask, ell_counts, R=32):
    """Each row block's width by hand: the smallest multiple of 128 lanes
    holding the live topics (one past the last non-zero count) of its real
    tokens' documents; 0 for a block with no real token."""
    cnt = np.asarray(ell_counts)
    extent = np.max(np.where(cnt > 0, np.arange(cnt.shape[1]) + 1, 0), 1)
    mask = np.asarray(token_mask) != 0
    live = np.where(mask, extent[np.asarray(token_doc)], 0)
    n, t = live.shape
    most = live.reshape(n, t // R, R).max(axis=2)
    real = mask.reshape(n, t // R, R).any(axis=2)
    return np.where(real, np.maximum(-(-most // 128), 1) * 128, 0)


@pytest.mark.parametrize("K,classes,n_widths", [
    # live extents about 20, 170 and 430 of P=640: widths 128, 256, 512
    (640, [(8, 20), (4, 200), (2, 700)], 3),
    # about 30, 290 and 570 of P=896: widths 128, 384, 640
    (896, [(6, 30), (3, 350), (2, 900)], 3),
    # every document reaches P=384: every block full width
    (384, [(3, 1500)], 1),
])
def test_lda_sample_heavy_tail_bit_exact(K, classes, n_widths):
    """Row blocks of several widths, over an ELL width that no width
    divides: draws and stats equal ``impl="ref"`` and ``sample_sweep``."""
    from repro.core import sampler as core
    corpus, shard, z, phi, phi_sum, cnts, tpcs, key = heavy_tail_case(
        K, classes)
    P = cnts.shape[1]
    assert P % 128 == 0 and P & (P - 1) != 0
    widths = expected_widths(shard.token_doc, shard.token_mask, cnts)
    assert len(np.unique(widths[widths > 0])) == n_widths
    n, t = z.shape
    kw = dict(alpha=50.0 / K, beta=0.01, num_words_total=corpus.num_words)
    args = (shard.tile_word, shard.token_doc, shard.token_mask, z, phi,
            phi_sum, cnts, tpcs, key)
    zk, sk = sample_ops.lda_sample(*args, impl="pallas", interpret=True, **kw)
    zr, sr = sample_ops.lda_sample(*args, impl="ref", interpret=True, **kw)
    # one chunk of every tile: the sweep's stats sum in the wrapper's order
    zs, ss = core.sample_sweep(phi, phi_sum, shard.tile_word,
                               shard.token_doc, shard.token_mask, z, cnts,
                               tpcs, key, tiles_per_step=n, **kw)
    np.testing.assert_array_equal(np.asarray(zk), np.asarray(zr))
    np.testing.assert_array_equal(np.asarray(zk), np.asarray(zs))
    assert 0 < float(sk.sparse_frac) < 1
    assert float(sk.sparse_frac) == float(sr.sparse_frac) == float(
        ss.sparse_frac)
    assert float(sk.mean_s_over_sq) == float(sr.mean_s_over_sq) == float(
        ss.mean_s_over_sq)
    assert float(sr.row_width_share) == float(ss.row_width_share) == 1.0
    share = widths[widths > 0].mean() / P
    assert float(sk.row_width_share) == pytest.approx(share, rel=1e-6)
    assert (share == 1.0) == (n_widths == 1)


@pytest.mark.parametrize("P", range(128, 2049, 128))
def test_chunk_prefix_is_the_full_prefix_sum(P):
    """The chunk tree over a W-lane row gives the P-lane prefix sum's first
    W lanes and its total bit for bit, for every W <= P in whole vregs;
    the W-lane sum's own last lane does not always."""
    from repro.core.sampler import prefix_sum
    from repro.kernels.lda_sample import kernel
    rng = np.random.default_rng(P)
    naive_differs = 0
    for W in range(128, P + 1, 128):
        nnz = rng.integers(1, W + 1, (64, 1))
        x = (rng.integers(1, 60, (64, W))
             * rng.random((64, W)) ** 4).astype(np.float32)
        x = np.where(np.arange(W) < nnz, x, np.float32(0))
        full = np.asarray(
            prefix_sum(jnp.asarray(np.pad(x, ((0, 0), (0, P - W))))))
        p1_cum, last = kernel.chunk_prefix(
            prefix_sum(jnp.asarray(x), stop=128), P)
        np.testing.assert_array_equal(np.asarray(p1_cum), full[:, :W])
        np.testing.assert_array_equal(np.asarray(last)[:, -1], full[:, -1])
        naive_differs += int((full[:, W - 1] != full[:, -1]).sum())
    # past 512 some W adds the chunk totals in another order than P does
    # (at P=384 both sum chunk 0 and chunk 1 in one add)
    if P > 512 and P & (P - 1):
        assert naive_differs > 0


def test_row_block_widths():
    """Each sampled block's width is the smallest whole-vreg width holding
    the live topics of its real tokens' documents.  Rows past a tile's
    real tokens still hold the previous tile's ELL rows (here, a document
    reaching P): they are ignored, as are the doc ids of padding slots."""
    from repro.kernels.lda_sample import kernel
    P, K, t = 640, 640, 64
    extent = np.array([0, 5, 128, 129, 400, 640])
    counts = (np.arange(P) < extent[:, None]).astype(np.int32)
    topics = np.tile(np.arange(P, dtype=np.int32), (len(extent), 1))
    # tile 0: blocks [docs 1, 5], [5]; tile 1: [2, 3, pads]; tile 2: [4], []
    token_doc = np.array([[1] * 16 + [5] * 48,
                          [2] * 8 + [3] * 8 + [5] * 48,
                          [4] * 20 + [5] * 44], np.int32)
    mask = np.zeros((3, t), np.int32)
    mask[0, :] = 1
    mask[1, :16] = 1
    mask[2, :20] = 1
    rng = np.random.default_rng(0)
    pstar = jnp.asarray(rng.random((3, K)), jnp.float32)
    u = jnp.asarray(rng.random((2, 3, t)), jnp.float32)
    *_, widths = kernel.lda_sample_tiles(
        jnp.arange(3), jnp.asarray(token_doc), pstar, jnp.asarray(counts),
        jnp.asarray(topics), u[0], u[1], jnp.asarray(mask),
        jnp.zeros((3, t), jnp.int32), alpha=0.1, interpret=True)
    want = [[640, 640], [256, 0], [512, 0]]
    np.testing.assert_array_equal(np.asarray(widths), want)
    np.testing.assert_array_equal(
        want, expected_widths(token_doc, mask, counts))
    share = float(kernel.row_width_share(widths, P))
    assert share == pytest.approx((640 + 640 + 256 + 512) / 4 / P)


@pytest.mark.parametrize("K,bits", [(1024, 10), (1000, 10), (2, 1)])
def test_packed_ell_round_trip(K, bits):
    """Packed ELL words give back every count and topic at the edges of
    their fields (count 0 to ``count_limit(K)``, topic 0 to K - 1), and
    the largest word stays a non-negative int32."""
    from repro.kernels.lda_sample import kernel
    top = kernel.count_limit(K)
    assert kernel.topic_bits(K) == bits and top == (1 << (31 - bits)) - 1
    counts = np.array([[0, 0, 1, 1, top, top, top - 1, 7]], np.int32)
    topics = np.array([[0, K - 1, 0, K - 1, 0, K - 1, K // 2, 1]], np.int32)
    for xp in (np, jnp):
        words = kernel.pack_ell(xp.asarray(counts), xp.asarray(topics), K)
        assert words.dtype == np.int32 and int(words.min()) >= 0
        c, k = kernel.unpack_ell(words, K)
        np.testing.assert_array_equal(np.asarray(c), counts)
        np.testing.assert_array_equal(np.asarray(k), topics)


@pytest.mark.parametrize("over", [0, 1])
@pytest.mark.parametrize("where", ["resolve_config", "shard"])
def test_count_field_guard(where, over):
    """The host refuses, under the Pallas sampler, a corpus whose longest
    document exceeds the packed count field, and accepts one exactly at
    it; the ``sq`` sampler, which packs nothing, accepts both."""
    import dataclasses

    from repro.core import trainer
    from repro.core.corpus import Corpus
    from repro.kernels.lda_sample import kernel
    K = 1024
    longest = kernel.count_limit(K) + over
    cfg = trainer.LDAConfig(num_topics=K, sampler="pallas", tile_tokens=32)
    if where == "resolve_config":
        corpus = Corpus(np.r_[np.zeros(longest, np.int32), 1, 1],
                        np.zeros(longest + 2, np.int32), 2, 1)
        check = lambda c: trainer.resolve_config(c, corpus)  # noqa: E731
    else:
        corpus, shard, z, *_ = setup_case(K, 32)
        lengths = np.asarray(shard.doc_length).copy()
        lengths[1] = longest
        shard = dataclasses.replace(shard, doc_length=lengths)
        check = lambda c: trainer._build_theta_ell(  # noqa: E731
            c, shard, z, None)
    if over:
        with pytest.raises(ValueError, match=str(kernel.count_limit(K))):
            check(cfg)
    else:
        check(cfg)
    check(dataclasses.replace(cfg, sampler="sq"))


def _collect_shapes(jaxpr, acc):
    """Every intermediate's shape, recursing into nested jaxprs (pjit,
    scan, cond, pallas_call kernels, ...), and the shapes of the nested
    jaxprs' inputs (a kernel's refs, its VMEM scratch among them)."""
    for v in jaxpr.invars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            acc.append(tuple(aval.shape))
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                acc.append(tuple(aval.shape))
        for p in eqn.params.values():
            subs = p if isinstance(p, (tuple, list)) else (p,)
            for sub in subs:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    _collect_shapes(sub.jaxpr, acc)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    _collect_shapes(sub, acc)
    return acc


def test_no_hbm_ell_gather():
    """The wrapper must not materialize the per-token (n, t, P) ELL tensor
    anywhere outside the kernel's per-tile VMEM working set: jaxpr shape
    accounting over the whole trace (ISSUE 5 acceptance criterion)."""
    corpus, shard, z, phi, phi_sum, cnts, tpcs, key = setup_case(128, 32)
    n, t = z.shape
    P = cnts.shape[1]
    kw = dict(alpha=0.5, beta=0.01, num_words_total=corpus.num_words)
    jaxpr = jax.make_jaxpr(
        lambda *a: sample_ops.lda_sample(*a, impl="pallas", interpret=True,
                                         **kw)
    )(shard.tile_word, shard.token_doc, shard.token_mask, z, phi, phi_sum,
      cnts, tpcs, key)
    shapes = _collect_shapes(jaxpr.jaxpr, [])
    assert n > 1  # the accounting below is vacuous otherwise
    P_lanes = -(-P // 128) * 128       # the kernel pads P to whole vregs
    bad = [s for s in shapes if len(s) == 3 and s[-1] in (P, P_lanes)
           and s[-2] == t and s[0] >= n]
    assert not bad, f"per-token HBM ELL gather reappeared: {bad}"
    # ... while the kernel's on-chip working set IS one tile's rows
    assert any(s == (t, 1, P_lanes) for s in shapes)


@pytest.mark.parametrize("K", [128, 256])
@pytest.mark.parametrize("tile_tokens", [16, 64])
def test_phi_update_kernel_matches_ref(K, tile_tokens):
    corpus, shard, z, phi, phi_sum, cnts, tpcs, key = setup_case(K, tile_tokens)
    dk = phi_ops.phi_update(shard.tile_word, shard.tile_first, z,
                            shard.token_mask, num_words=corpus.num_words,
                            num_topics=K, impl="pallas",
                            interpret=True)
    dr = phi_ops.phi_update(shard.tile_word, shard.tile_first, z,
                            shard.token_mask, num_words=corpus.num_words,
                            num_topics=K, impl="ref",
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))
    assert int(dk.sum()) == corpus.num_tokens


@pytest.mark.parametrize("K", [128, 192])
def test_phi_delta_kernel_matches_ref(K):
    """Incremental MXU update == signed scatter oracle == rebuild diff."""
    corpus, shard, z, phi, phi_sum, cnts, tpcs, key = setup_case(K, 16)
    n, t = z.shape
    z_new = jax.random.randint(jax.random.key(9), (n, t), 0, K,
                               jnp.int32).astype(z.dtype)
    dk = phi_ops.phi_delta(shard.tile_word, shard.tile_first, z, z_new,
                           shard.token_mask, num_words=corpus.num_words,
                           num_topics=K, impl="pallas",
                            interpret=True)
    dr = phi_ops.phi_delta(shard.tile_word, shard.tile_first, z, z_new,
                           shard.token_mask, num_words=corpus.num_words,
                           num_topics=K, impl="ref",
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))
    want = (updates.phi_from_z(z_new, shard.tile_word, shard.token_mask,
                               corpus.num_words, K)
            - updates.phi_from_z(z, shard.tile_word, shard.token_mask,
                                 corpus.num_words, K))
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(want))
    assert int(dk.sum()) == 0  # moves conserve the token count


def test_phi_update_heavy_word_spanning_tiles():
    """Words spanning many tiles (Zipf head) accumulate across revisits."""
    corpus = zipf_corpus(num_docs=30, num_words=20, avg_doc_len=60, seed=5)
    shard = tile_corpus(corpus, 1, tile_tokens=8)[0]  # tiny tiles -> many revisits
    K = 128
    n, t = shard.token_doc.shape
    z = jax.random.randint(jax.random.key(1), (n, t), 0, K, jnp.int32)
    dk = phi_ops.phi_update(shard.tile_word, shard.tile_first, z,
                            shard.token_mask, num_words=corpus.num_words,
                            num_topics=K, impl="pallas",
                            interpret=True)
    dr = phi_ops.phi_update(shard.tile_word, shard.tile_first, z,
                            shard.token_mask, num_words=corpus.num_words,
                            num_topics=K, impl="ref",
                            interpret=True)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))


def test_kernel_iteration_converges(tiny_corpus):
    """Full trainer iteration driven by the Pallas kernels end-to-end."""
    from repro.core import trainer
    K = 128
    cfg = trainer.LDAConfig(num_topics=K, tile_tokens=32, tiles_per_step=8)
    shard = tile_corpus(tiny_corpus, 1, 32)[0]
    key = jax.random.key(0)
    state = trainer.init_state(cfg, shard, key)
    P = ell_capacity(tiny_corpus, K)
    kw = dict(alpha=cfg.resolved_alpha(), beta=cfg.beta,
              num_words_total=tiny_corpus.num_words)
    lls = []
    for it in range(6):
        theta = updates.theta_from_z(state.z, shard.token_doc,
                                     shard.token_mask, shard.num_docs_local, K)
        cnts, tpcs, _ = updates.theta_to_ell(theta, P)
        z_new, _ = sample_ops.lda_sample(
            shard.tile_word, shard.token_doc, shard.token_mask, state.z,
            state.phi_vk, state.phi_sum, cnts, tpcs,
            jax.random.fold_in(key, it), impl="pallas", interpret=True, **kw)
        phi = state.phi_vk + phi_ops.phi_delta(
            shard.tile_word, shard.tile_first, state.z, z_new,
            shard.token_mask, num_words=tiny_corpus.num_words, num_topics=K,
            impl="pallas", interpret=True)
        state = trainer.LDAState(z=z_new, phi_vk=phi, phi_sum=phi.sum(0),
                                 iteration=state.iteration + 1)
        ll = float(trainer.log_likelihood(cfg, shard, state)) / tiny_corpus.num_tokens
        lls.append(ll)
    assert lls[-1] > lls[0] + 0.2, lls
