"""Fold-in Pallas kernel (repro.kernels.fold_in) vs its jnp oracle, which
is also the XLA serving path: the two must be draw-identical given the same
key (same split tree, same uniforms, same tie-breaking in the ELL top-k).

Kernel runs in interpret mode (CPU container); the bit-exactness contract is
the same one the TPU build must satisfy.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.serve import (EngineConfig, HotSwapModel, InferConfig,
                         LDAServeEngine, ModelSnapshot)
from repro.serve.infer import fold_in, pack_docs

V, WORDS_PER_TOPIC = 64, 8


def planted_case(K, num_docs, doc_len, seed=0, length=None):
    """Planted-mixture corpus against a disjoint-support frozen model:
    topic k owns words [k*8, (k+1)*8); docs mix two topics 75/25."""
    n_topics = min(K, V // WORDS_PER_TOPIC)
    phi = np.zeros((V, K), np.int32)
    for k in range(n_topics):
        phi[k * WORDS_PER_TOPIC:(k + 1) * WORDS_PER_TOPIC, k] = 200
    rng = np.random.default_rng(seed)
    docs, majors = [], []
    for _ in range(num_docs):
        a, b = rng.choice(n_topics, size=2, replace=False)
        mix = rng.choice([a, b], size=doc_len, p=[0.75, 0.25])
        words = mix * WORDS_PER_TOPIC + rng.integers(0, WORDS_PER_TOPIC,
                                                     doc_len)
        docs.append(words.astype(np.int32))
        majors.append(int(a))
    tokens, mask = pack_docs(docs, length)
    snap = ModelSnapshot(phi_vk=jnp.asarray(phi),
                         phi_sum=jnp.asarray(phi.sum(0)),
                         alpha=0.1, beta=0.01, num_words_total=V)
    return snap, tokens, mask, np.asarray(majors)


def run_impl(snap, tokens, mask, impl, key=None, alpha=None, **kw):
    kw.setdefault("burn_in", 6)
    kw.setdefault("samples", 3)
    kw.setdefault("top_k", 4)
    return fold_in(snap.phi_vk, snap.phi_sum, tokens, mask,
                   key if key is not None else jax.random.key(7),
                   alpha if alpha is not None else snap.alpha, snap.beta,
                   num_words_total=snap.num_words_total, impl=impl, **kw)


# K = 8: planted topics exactly; 128: one search block; 96: fallback block
@pytest.mark.parametrize("K", [8, 96, 128])
def test_pallas_matches_ref_and_xla_bit_for_bit(K):
    snap, tokens, mask, _ = planted_case(K, num_docs=12, doc_len=40, seed=3)
    out = {impl: run_impl(snap, tokens, mask, impl)
           for impl in ("xla", "pallas")}
    for impl in ("pallas",):
        np.testing.assert_array_equal(np.asarray(out["xla"].theta),
                                      np.asarray(out[impl].theta))
        np.testing.assert_array_equal(np.asarray(out["xla"].top_topics),
                                      np.asarray(out[impl].top_topics))
        np.testing.assert_array_equal(np.asarray(out["xla"].top_weights),
                                      np.asarray(out[impl].top_weights))
        np.testing.assert_array_equal(np.asarray(out["xla"].sparse_frac),
                                      np.asarray(out[impl].sparse_frac))
        # the one non-bit-exact field: S/(S+Q) is accumulated per doc in the
        # kernel but summed over the whole (B, L) batch in the XLA path —
        # float reduction order differs by design, so ulp-level only
        np.testing.assert_allclose(np.asarray(out["xla"].mean_s_over_sq),
                                   np.asarray(out[impl].mean_s_over_sq),
                                   rtol=1e-6)


def test_pallas_parity_under_padding():
    """Docs shorter than the length bucket: masked slots stay inert and
    parity holds through the padding path the engine actually exercises."""
    snap, tokens, mask, _ = planted_case(8, num_docs=5, doc_len=18, seed=5,
                                         length=32)
    assert not mask.all()
    a = run_impl(snap, tokens, mask, "xla")
    b = run_impl(snap, tokens, mask, "pallas")
    np.testing.assert_array_equal(np.asarray(a.theta), np.asarray(b.theta))
    np.testing.assert_allclose(np.asarray(b.theta).sum(1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("ell_capacity", [100, 128])
def test_pallas_parity_at_configured_ell_capacity(ell_capacity):
    """A configured ELL capacity off the 128-lane grid: the kernel pads its
    slice to whole vregs but must draw from exactly ``ell_capacity`` live
    topics, as the XLA path does.  Random z0 over K=256 gives each
    256-token doc more live topics than the capacity."""
    K, L = 256, 256
    rng = np.random.default_rng(2)
    phi = rng.integers(1, 50, size=(V, K)).astype(np.int32)
    snap = ModelSnapshot(phi_vk=jnp.asarray(phi),
                         phi_sum=jnp.asarray(phi.sum(0)),
                         alpha=0.1, beta=0.01, num_words_total=V)
    docs = [rng.integers(0, V, L).astype(np.int32) for _ in range(2)]
    tokens, mask = pack_docs(docs, L)
    kw = dict(burn_in=2, samples=1, top_k=8, ell_capacity=ell_capacity)
    a = run_impl(snap, tokens, mask, "xla", **kw)
    b = run_impl(snap, tokens, mask, "pallas", **kw)
    for field in ("theta", "top_topics", "top_weights", "sparse_frac"):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)))


def test_pallas_recovers_planted_mixture():
    """The kernel path is not just self-consistent — it solves the task."""
    snap, tokens, mask, majors = planted_case(8, num_docs=16, doc_len=48,
                                              seed=11)
    res = run_impl(snap, tokens, mask, "pallas", burn_in=8, samples=4)
    got = np.asarray(res.theta).argmax(1)
    assert (got == majors).mean() >= 0.9, (got, majors)


def test_pallas_hyperparam_hotswap_does_not_recompile():
    """alpha/beta enter the kernel as data (a (1,2) array), so a snapshot
    with different hyperparams must reuse the compiled variant."""
    snap, tokens, mask, _ = planted_case(8, num_docs=4, doc_len=20, seed=1)
    run_impl(snap, tokens, mask, "pallas", alpha=0.1)
    c0 = fold_in._cache_size()
    run_impl(snap, tokens, mask, "pallas", alpha=0.5)
    assert fold_in._cache_size() == c0


def test_engine_serves_pallas_impl_end_to_end():
    snap, _, _, _ = planted_case(8, num_docs=1, doc_len=8)
    eng = LDAServeEngine(
        HotSwapModel(snap),
        EngineConfig(max_batch=4, max_delay_ms=50.0, length_buckets=(32,),
                     infer=InferConfig(burn_in=3, samples=2, impl="pallas")))
    try:
        docs = [np.arange(k * WORDS_PER_TOPIC, k * WORDS_PER_TOPIC + 8,
                          dtype=np.int32) for k in (0, 1, 2)]
        out = eng.infer_many(docs)
        got = [int(r["theta"].argmax()) for r in out]
        assert got == [0, 1, 2], got
    finally:
        eng.stop()
