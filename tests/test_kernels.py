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


def _collect_shapes(jaxpr, acc):
    """Every intermediate's shape, recursing into nested jaxprs (pjit,
    scan, cond, pallas_call kernels, ...)."""
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
