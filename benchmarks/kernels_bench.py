"""Pallas kernel microbenchmarks + ref-path timings.  Off the TPU the
kernels run in interpret mode: those rows time the interpreter, not a
device (the ``interpret`` field says which)."""
import jax

from .common import emit, timeit


def run():
    import jax.numpy as jnp
    from repro.core import updates
    from repro.core.corpus import ell_capacity, tile_corpus
    from repro.data.synthetic import zipf_corpus
    from repro.kernels import resolve_interpret
    from repro.kernels.lda_sample import ops as sample_ops
    from repro.kernels.phi_update import ops as phi_ops

    corpus = zipf_corpus(num_docs=48, num_words=200, avg_doc_len=60, seed=0)
    K = 256
    shard = tile_corpus(corpus, 1, 64)[0]
    n, t = shard.token_doc.shape
    key = jax.random.key(0)
    z = jax.random.randint(key, (n, t), 0, K, jnp.int32).astype(jnp.int16)
    phi = updates.phi_from_z(z, shard.tile_word, shard.token_mask,
                             corpus.num_words, K)
    theta = updates.theta_from_z(z, shard.token_doc, shard.token_mask,
                                 shard.num_docs_local, K)
    cnts, tpcs, _ = updates.theta_to_ell(theta, ell_capacity(corpus, K))
    kw = dict(alpha=50.0 / K, beta=0.01, num_words_total=corpus.num_words)

    interpret = resolve_interpret()
    z2 = jax.random.randint(jax.random.key(1), z.shape, 0, K,
                            jnp.int32).astype(jnp.int16)
    for impl in ("ref", "pallas"):
        us = timeit(lambda: sample_ops.lda_sample(
            shard.tile_word, shard.token_doc, shard.token_mask, z, phi,
            phi.sum(0), cnts, tpcs, key, impl=impl, interpret=interpret,
            **kw)[0])
        emit(f"kernel_lda_sample_{impl}", us,
             f"tokens={corpus.num_tokens};"
             f"interpret={interpret and impl == 'pallas'}")
        us = timeit(lambda: phi_ops.phi_update(
            shard.tile_word, shard.tile_first, z, shard.token_mask,
            num_words=corpus.num_words, num_topics=K, impl=impl,
            interpret=interpret))
        emit(f"kernel_phi_update_{impl}", us, f"K={K};V={corpus.num_words}")
        us = timeit(lambda: phi_ops.phi_delta(
            shard.tile_word, shard.tile_first, z, z2, shard.token_mask,
            num_words=corpus.num_words, num_topics=K, impl=impl,
            interpret=interpret))
        emit(f"kernel_phi_delta_{impl}", us, f"K={K};V={corpus.num_words}")
