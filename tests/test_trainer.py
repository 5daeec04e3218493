"""End-to-end training behaviour: convergence vs the sequential oracle
(paper Fig. 8 analogue), schedules, likelihood correctness."""
import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import likelihood, seq_ref, trainer


class TestConvergence:
    ITERS = 25

    @pytest.fixture(scope="class")
    def corpus(self):
        from repro.data.synthetic import lda_corpus
        return lda_corpus(num_docs=40, num_words=96, num_topics=8,
                          avg_doc_len=36, seed=1)

    @pytest.fixture(scope="class")
    def seq_lls(self, corpus):
        lls = []
        for it, z, theta, phi in seq_ref.train(corpus, 8, self.ITERS):
            if it == self.ITERS - 1:
                ll = float(likelihood.joint_log_likelihood(
                    jnp.asarray(theta), jnp.asarray(corpus.doc_lengths()),
                    jnp.asarray(phi.T), jnp.asarray(phi.sum(1)),
                    50.0 / 8, 0.01)) / corpus.num_tokens
                lls.append(ll)
        return lls

    def test_sq_converges_toward_oracle(self, corpus, seq_lls):
        cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8)
        res = trainer.train(corpus, cfg, self.ITERS, eval_every=self.ITERS)
        ll0 = res.ll_per_token[0]
        # delayed-count CGS trails exact CGS but must land in its vicinity
        assert ll0 > seq_lls[-1] - 0.55, (ll0, seq_lls)

    def test_ll_monotone_trend(self, corpus):
        cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8)
        res = trainer.train(corpus, cfg, 16, eval_every=4)
        assert res.ll_per_token[-1] > res.ll_per_token[0] + 0.3

    def test_dense_and_sq_converge_similarly(self, corpus):
        cfg_s = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8)
        cfg_d = dataclasses.replace(cfg_s, sampler="dense")
        ll_s = trainer.train(corpus, cfg_s, 15, eval_every=15).ll_per_token[-1]
        ll_d = trainer.train(corpus, cfg_d, 15, eval_every=15).ll_per_token[-1]
        assert abs(ll_s - ll_d) < 0.35, (ll_s, ll_d)

    def test_workschedule2_converges(self, corpus):
        cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8,
                                micro_chunks=4)
        res = trainer.train(corpus, cfg, 15, eval_every=15)
        assert res.ll_per_token[-1] > -5.2

    def test_sparse_fraction_grows(self, corpus):
        """The paper's Fig. 7 effect: theta sparsifies, p1 hit rate rises."""
        cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8)
        res = trainer.train(corpus, cfg, 12, eval_every=12)
        early = res.stats[0][0]
        late = res.stats[-1][0]
        assert late >= early - 0.05  # non-decreasing (within noise)


class TestPallasSamplerParity:
    """`sampler="pallas"` is the same Markov chain as `"sq"`, bit for bit
    (ISSUE 5 acceptance criterion), for both work schedules."""

    @pytest.fixture(scope="class")
    def corpus(self):
        from repro.data.synthetic import lda_corpus
        return lda_corpus(num_docs=24, num_words=48, num_topics=4,
                          avg_doc_len=30, seed=3)

    def _parity(self, corpus, K, micro, topic_dtype=jnp.int16, iters=2):
        import jax
        from repro.core.corpus import ell_capacity, tile_corpus
        from repro.core import updates
        shard = tile_corpus(corpus, 1, 16)[0]
        cfg_s = trainer.LDAConfig(num_topics=K, tile_tokens=16,
                                  tiles_per_step=4, micro_chunks=micro,
                                  topic_dtype=topic_dtype,
                                  ell_capacity=ell_capacity(corpus, K))
        cfg_p = dataclasses.replace(cfg_s, sampler="pallas")
        key = jax.random.key(0)
        st_s = trainer.init_state(cfg_s, shard, key)
        st_p = st_s
        for _ in range(iters):
            st_s, is_s = trainer.lda_iteration(cfg_s, shard, st_s, key)
            st_p, is_p = trainer.lda_iteration(cfg_p, shard, st_p, key)
            np.testing.assert_array_equal(np.asarray(st_s.z), np.asarray(st_p.z))
            np.testing.assert_array_equal(np.asarray(st_s.phi_vk),
                                          np.asarray(st_p.phi_vk))
            assert st_p.z.dtype == topic_dtype
            assert abs(float(is_s.mean_s_over_sq)
                       - float(is_p.mean_s_over_sq)) < 1e-5
            assert abs(float(is_s.sparse_frac)
                       - float(is_p.sparse_frac)) < 1e-5
        # the incremental phi advance keeps the rebuild invariant exactly
        phi2 = updates.phi_from_z(st_p.z, shard.tile_word, shard.token_mask,
                                  corpus.num_words, K)
        np.testing.assert_array_equal(np.asarray(phi2), np.asarray(st_p.phi_vk))

    def test_ws1_bit_identical(self, corpus):
        self._parity(corpus, K=128, micro=1)

    def test_ws2_bit_identical(self, corpus):
        self._parity(corpus, K=128, micro=3)  # n % 3 != 0 exercises padding

    def test_odd_K_int32(self, corpus):
        """Non-128-multiple K (fallback search block) + int32 z."""
        self._parity(corpus, K=96, micro=1, topic_dtype=jnp.int32, iters=1)

    def test_pallas_converges(self, corpus):
        cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8,
                                sampler="pallas")
        res = trainer.train(corpus, cfg, 10, eval_every=2)
        assert res.ll_per_token[-1] > res.ll_per_token[0] + 0.2, res.ll_per_token

    def test_fit_reports_row_width_share(self):
        """Short documents beside one of 300 tokens: P=256, and the row
        blocks of the short documents' words sample 128 lanes, so the
        mean width share lies strictly between 1/2 and 1; `sq` reads 1."""
        from repro.core.corpus import Corpus
        from repro.train import fit
        rng = np.random.default_rng(0)
        lens = np.array([20] * 24 + [300])
        doc = np.repeat(np.arange(len(lens)), lens).astype(np.int32)
        # the long document's words are its own, so its tiles are apart
        word = np.where(doc < 24, rng.integers(0, 16, len(doc)),
                        rng.integers(16, 24, len(doc))).astype(np.int32)
        corpus = Corpus(doc, word, len(lens), 24)
        shares = {}
        for name in ("sq", "pallas"):
            cfg = trainer.LDAConfig(num_topics=256, tile_tokens=32,
                                    tiles_per_step=8, sampler=name)
            res = fit(corpus, cfg, 2, eval_every=2)
            shares[name] = [s[3] for s in res.stats]
        assert shares["sq"] == [1.0, 1.0]
        assert all(0.5 < s < 1.0 for s in shares["pallas"]), shares


class TestTopicDtypeGuard:
    """Regression (dtype-flow DT001): K beyond topic_dtype's range used to
    wrap z silently in init_state; the config now rejects it up front."""

    def test_k_too_large_for_int16_rejected(self):
        with pytest.raises(ValueError, match="does not fit"):
            trainer.LDAConfig(num_topics=(1 << 15) + 1)

    def test_int32_escape_hatch(self):
        cfg = trainer.LDAConfig(num_topics=(1 << 15) + 1,
                                topic_dtype=jnp.int32)
        assert cfg.num_topics == (1 << 15) + 1

    def test_non_integer_dtype_rejected(self):
        with pytest.raises(ValueError, match="integer dtype"):
            trainer.LDAConfig(num_topics=8, topic_dtype=jnp.float32)

    def test_boundary_k_fits(self):
        trainer.LDAConfig(num_topics=1 << 15)   # K-1 == int16 max: fine


def test_sweep_draws_invariant_to_tiles_per_step(tiny_corpus):
    """jax.random.split is not prefix-stable: splitting after padding made
    every draw depend on the chunk width through n_pad.  Keys now split over
    the unpadded tile count — pinned across two widths for both samplers."""
    import jax

    def one_iter(sampler_name, width):
        cfg = trainer.LDAConfig(num_topics=16, tile_tokens=32,
                                tiles_per_step=width, sampler=sampler_name)
        from repro.core.corpus import ell_capacity, tile_corpus
        cfg = dataclasses.replace(
            cfg, ell_capacity=ell_capacity(tiny_corpus, 16))
        shard = tile_corpus(tiny_corpus, 1, 32)[0]
        state = trainer.init_state(cfg, shard, jax.random.key(0))
        state, _ = trainer.lda_iteration(cfg, shard, state, jax.random.key(0))
        return np.asarray(state.z)

    for name in ("sq", "dense", "pallas"):
        np.testing.assert_array_equal(one_iter(name, 8), one_iter(name, 5))


def test_train_reports_compile_time_separately(tiny_corpus):
    """Iteration 0 must not carry jit compile time (it used to pollute the
    first row of every throughput trajectory)."""
    cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8)
    res = trainer.train(tiny_corpus, cfg, 4, eval_every=4)
    assert res.compile_sec > 0
    assert len(res.tokens_per_sec) == 4
    # compiled-step timings: the first row is in family with the rest, not
    # compile-dominated (generous 20x bound vs the best row)
    assert res.tokens_per_sec[0] > max(res.tokens_per_sec) / 20, res.tokens_per_sec


def test_likelihood_direct():
    """Tiny case vs straight lgamma arithmetic in pure python."""
    import math
    theta = np.array([[2, 0], [1, 3]], np.int64)
    dl = theta.sum(1)
    phi = np.array([[1, 1], [1, 3]], np.int64)  # K x V
    phi_sum = phi.sum(1)
    a, b = 0.5, 0.1
    K, V = 2, 2

    def lg(x):
        return math.lgamma(x)

    want = 0.0
    for d in range(2):
        want += lg(K * a) - lg(dl[d] + K * a)
        for k in range(K):
            want += lg(theta[d, k] + a) - lg(a)
    for k in range(K):
        want += lg(V * b) - lg(phi_sum[k] + V * b)
        for v in range(V):
            want += lg(phi[k, v] + b) - lg(b)

    got = float(likelihood.joint_log_likelihood(
        jnp.asarray(theta), jnp.asarray(dl), jnp.asarray(phi.T),
        jnp.asarray(phi_sum), a, b))
    assert abs(got - want) < 1e-3, (got, want)


def test_tokens_per_sec_reported(tiny_corpus):
    cfg = trainer.LDAConfig(num_topics=8, tile_tokens=32, tiles_per_step=8)
    res = trainer.train(tiny_corpus, cfg, 3, eval_every=3)
    assert len(res.tokens_per_sec) == 3
    assert all(t > 0 for t in res.tokens_per_sec)
