"""jit-cache: compile-count audit over the public jitted entry points.

Each audit runs a config matrix against one entry point and measures cache
growth via the repo's own cache probes (``serve_cache_size``,
``fn._cache_size()``):

- **JIT001** — first pass compiles MORE than the declared budget: some
  supposedly-shared config is fragmenting the cache (an unstable static
  arg, a shape leak through a static, ...).
- **JIT002** — a REPEAT of the identical matrix grows the cache again: a
  trace leak — something unhashed varies between identical calls (python
  object identity in a static, a fresh closure per call, ...).
- **JIT003** — a static argument is unhashable: the call raises TypeError
  before tracing.

Budgets are ceilings, not exact counts, so the audit is idempotent in a
warm process (pytest may have compiled some variants already; the deltas
only shrink).  All audits run tiny odd shapes nothing else compiles, with
``interpret=True`` pinned for every impl so the static tuple is constant.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Callable

from .report import Finding

CHECKER = "jit-cache"


@dataclasses.dataclass
class JitAudit:
    """One entry-point × config-matrix audit."""

    name: str                        # scope in fingerprints
    path: str                        # repo-relative file findings anchor to
    cache_size: Callable[[], int]
    run: Callable[[], None]          # execute the full matrix once
    max_compiles: int                # declared budget for one cold pass


def audit_one(audit: JitAudit) -> list[Finding]:
    findings = []

    def emit(code, message):
        findings.append(Finding(checker=CHECKER, code=code, path=audit.path,
                                line=1, scope=audit.name, message=message))

    before = audit.cache_size()
    try:
        audit.run()
    except TypeError as e:
        if "unhashable" in str(e):
            emit("JIT003",
                 f"unhashable static argument in '{audit.name}': {e}")
            return findings
        raise
    d1 = audit.cache_size() - before
    if d1 > audit.max_compiles:
        emit("JIT001",
             f"'{audit.name}' compiled {d1} variants for its config matrix "
             f"(budget {audit.max_compiles}) — a static arg is fragmenting "
             f"the jit cache")
    audit.run()
    d2 = audit.cache_size() - before - d1
    if d2 != 0:
        emit("JIT002",
             f"'{audit.name}' recompiled {d2} variant(s) on an identical "
             f"repeat of the matrix — trace leak from an unstable static "
             f"arg")
    return findings


# ---------------------------------------------------------------------------
# The declared audits.  Built lazily: importing this module must not import
# jax (the prng/lock checkers run without it).
# ---------------------------------------------------------------------------

def _serve_buffer_audit() -> JitAudit:
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import infer

    V, K = 37, 24
    phi = (np.arange(V * K, dtype=np.int32).reshape(V, K) % 7) + 1
    phi_vk = jnp.asarray(phi)
    phi_sum = jnp.asarray(phi.sum(0, dtype=np.int32))
    hyper = jnp.asarray([0.1, 0.01], jnp.float32)
    buckets = ((2, 12), (3, 12), (2, 20))
    impls = ("xla", "pallas")

    def run():
        for B, L in buckets:
            docs = [np.arange(1 + (i % L), dtype=np.int64) % V
                    for i in range(B)]
            buf = jnp.asarray(infer.pack_request_buffer(docs, B, L, seed=7))
            for impl in impls:
                infer.fold_in_buffer(
                    phi_vk, phi_sum, buf, hyper, num_words_total=V,
                    burn_in=1, samples=1, top_k=4, impl=impl,
                    interpret=True)

    return JitAudit(
        name="serve.fold_in_buffer[impl x bucket]",
        path="src/repro/serve/infer.py",
        cache_size=infer.serve_cache_size, run=run,
        max_compiles=len(buckets) * len(impls))


def _serve_sharded_audit() -> JitAudit:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import infer
    from repro.serve.snapshot import ModelSnapshot, shard_snapshot

    V, K = 41, 16
    phi = (np.arange(V * K, dtype=np.int32).reshape(V, K) % 5) + 1
    snap = ModelSnapshot(
        phi_vk=jnp.asarray(phi),
        phi_sum=jnp.asarray(phi.sum(0, dtype=np.int32)),
        alpha=0.1, beta=0.01, num_words_total=V)
    ssnap = shard_snapshot(snap, 1)
    B, L = 2, 10
    tokens = np.arange(B * L, dtype=np.int32).reshape(B, L) % V
    mask = np.ones((B, L), bool)
    mask[1, 7:] = False
    key = jax.random.key(3)

    def run():
        for comm in ("psum", "all2all"):
            cfg = infer.InferConfig(burn_in=1, samples=1, top_k=4, comm=comm)
            infer.fold_in_sharded(ssnap, tokens, mask, key, cfg,
                                  interpret=True)

    return JitAudit(
        name="serve.fold_in_sharded[comm matrix]",
        path="src/repro/serve/infer.py",
        cache_size=infer.serve_cache_size, run=run, max_compiles=2)


def _train_sweep_audit() -> JitAudit:
    import jax
    import numpy as np

    from repro.kernels.lda_sample import ops as lda_ops

    n, t, V, K, D = 4, 8, 6, 16, 5
    tile_word = (np.arange(n, dtype=np.int32) % V)
    token_doc = ((np.arange(n * t).reshape(n, t) * 3) % D).astype(np.int32)
    token_mask = np.ones((n, t), np.int32)
    z = np.zeros((n, t), np.int32)
    phi = np.ones((V, K), np.int32)
    phi_sum = np.full((K,), V, np.int32)
    P = 3
    ell_counts = np.zeros((D, P), np.int32)
    ell_topics = np.zeros((D, P), np.int32)
    key = jax.random.key(5)

    def run():
        for impl in ("pallas", "ref"):
            lda_ops.lda_sample(
                tile_word, token_doc, token_mask, z, phi, phi_sum,
                ell_counts, ell_topics, key,
                alpha=0.5, beta=0.01, num_words_total=V,
                impl=impl, interpret=True)

    return JitAudit(
        name="train.lda_sample[impl matrix]",
        path="src/repro/kernels/lda_sample/ops.py",
        cache_size=lda_ops.lda_sample._cache_size, run=run, max_compiles=2)


def _train_sharded_sweep_audit() -> JitAudit:
    """The sharded-sampler matrix: one kernel compile per shard GEOMETRY,
    never per shard index or shard count.  build_shards pads every shard of
    a partition to a common tile count, so running the fused sweep over
    each shard of 1-, 2- and 4-way partitions must land on at most one
    compile per distinct (n, t, D) signature — a recompile across shard
    counts here is exactly the cache leak that would multiply mesh compile
    time by the device count."""
    import jax
    import numpy as np

    from repro.core.corpus import Corpus
    from repro.distributed import partition
    from repro.kernels.lda_sample import ops as lda_ops

    D, V, per_doc, K, t = 12, 18, 14, 16, 8
    rng = np.random.default_rng(7)
    doc_ids = np.repeat(np.arange(D, dtype=np.int32), per_doc)
    word_ids = rng.integers(0, V, D * per_doc).astype(np.int32)
    corpus = Corpus(doc_ids, word_ids, D, V)
    key = jax.random.key(5)
    shard_counts = (1, 2, 4)
    P = 3

    cases = []   # the shards of each partition
    geometries = set()
    for S in shard_counts:
        shards, _, _ = partition.build_shards(corpus, S, 1, "1d", t)
        cases.append(shards)
        d_max = max(s.num_docs_local for s in shards)
        geometries.add((shards[0].tile_word.shape[0], d_max))

    def run():
        for shards in cases:
            d_max = max(s.num_docs_local for s in shards)
            ell_c = np.zeros((d_max, P), np.int32)
            ell_t = np.zeros((d_max, P), np.int32)
            for s in shards:
                phi = np.ones((s.num_words, K), np.int32)
                phi_sum = np.full((K,), s.num_words, np.int32)
                lda_ops.lda_sample(
                    s.tile_word, s.token_doc, s.token_mask,
                    np.zeros(s.token_doc.shape, np.int32), phi, phi_sum,
                    ell_c, ell_t, key,
                    alpha=0.5, beta=0.01, num_words_total=V,
                    impl="pallas", interpret=True)

    return JitAudit(
        name="train.lda_sample[sharded geometry matrix]",
        path="src/repro/kernels/lda_sample/ops.py",
        cache_size=lda_ops.lda_sample._cache_size, run=run,
        max_compiles=len(geometries))


def _serve_engine_audit() -> JitAudit:
    """The continuous-batching engine end to end: live traffic across the
    (B, L) bucket matrix — including an injected device-OOM whose fallback
    re-dispatches at a *smaller* batch bucket — must stay inside the bucket
    budget.  This is the scheduler-level twin of the fold_in_buffer audit:
    admission, deadline reaping and OOM splitting may only ever land on
    bucket shapes already in the matrix, never mint new compiles."""
    import numpy as np

    from repro.serve import infer
    from repro.serve.engine import EngineConfig, LDAServeEngine
    from repro.serve.faults import FaultPlan
    from repro.serve.snapshot import HotSwapModel, ModelSnapshot

    import jax.numpy as jnp

    V, K = 29, 8
    phi = (np.arange(V * K, dtype=np.int32).reshape(V, K) % 5) + 1
    snap = ModelSnapshot(
        phi_vk=jnp.asarray(phi),
        phi_sum=jnp.asarray(phi.sum(0, dtype=np.int32)),
        alpha=0.1, beta=0.01, num_words_total=V)
    icfg = infer.InferConfig(burn_in=1, samples=1, top_k=4)

    def _round(cfg: EngineConfig, docs):
        eng = LDAServeEngine(HotSwapModel(snap), cfg)
        try:
            eng.infer_many(docs, timeout=60.0)
        finally:
            eng.stop()

    def run():
        base = dict(max_delay_ms=100.0, length_buckets=(8, 16), infer=icfg)
        # full batch -> bucket (4, 8)
        _round(EngineConfig(max_batch=4, **base),
               [np.arange(5, dtype=np.int64) % V for _ in range(4)])
        # single long doc -> bucket (1, 16)
        _round(EngineConfig(max_batch=1, **base),
               [np.arange(12, dtype=np.int64) % V])
        # injected OOM (initial try + 1 retry both fail) -> the fallback
        # splits the 4-doc batch into two (2, 8)-bucket halves
        _round(EngineConfig(max_batch=4, oom_backoff_ms=0.5,
                            fault_plan=FaultPlan.parse("device_oom@0x2"),
                            **base),
               [np.arange(6, dtype=np.int64) % V for _ in range(4)])

    return JitAudit(
        name="serve.engine[bucket matrix + oom fallback]",
        path="src/repro/serve/engine.py",
        cache_size=infer.serve_cache_size, run=run,
        max_compiles=3)   # shapes (4,8), (1,16), (2,8)


def run(root: Path) -> list[Finding]:
    findings = []
    for build in (_serve_buffer_audit, _serve_sharded_audit,
                  _serve_engine_audit, _train_sweep_audit,
                  _train_sharded_sweep_audit):
        findings += audit_one(build())
    return findings
