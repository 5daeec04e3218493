"""LDA training loop — WorkSchedule1/2 (paper §5.1) on JAX meshes.

State layout:
  z        (n_tiles, tile_tokens) int16 — topic assignments (C7 compression);
           the *only* mutable model state: theta and phi are derived counts,
           rebuilt exactly from z (this is also what makes checkpoints tiny
           and elastic — see repro.distributed.checkpoint).
  phi_vk   (V_local, K) int32 — topic-word counts, word-major.
  phi_sum  (K,) int32 — global per-topic totals.

Per iteration (delayed-count semantics, exactly the paper's):
  1. theta/ELL rebuilt from z (psum over "model" in 2D mode);
  2. every token resampled against the frozen iteration-start phi
     (WorkSchedule1: one sweep; WorkSchedule2: M micro-chunks scanned with
     theta refreshed in between — fresher counts, the streaming analogue of
     the paper's chunk pipeline);
  3. phi advanced **incrementally**: one ``updates.phi_delta`` scatter pass
     over the sweep's moves, added to the iteration-start phi (exact in int
     arithmetic — ``phi_old + delta == rebuild(z_new)``), then replicas
     reduced+broadcast (psum, C3).  ``compressed_sync`` all-reduces the same
     delta in int16, with an int32 correction for the rows whose corpus
     flux can overflow it (``heavy_rows``).

Sampler backends (``LDAConfig.sampler``):
  * ``"sq"``     — the paper's sparsity-aware S/Q sampler as an XLA scan
                   (repro.core.sampler);
  * ``"pallas"`` — the fused ``repro.kernels.lda_sample`` sweep: one word
                   tile per grid step, its phi row and each token's ELL row
                   DMA'd on-chip, draws bit-identical to ``"sq"`` under the
                   same key; count updates go through the
                   ``repro.kernels.phi_update`` MXU kernel;
  * ``"dense"``  — the O(K) baseline.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import resolve_interpret
from . import dense_sampler, likelihood, sampler, sync, updates
from .corpus import Corpus, TiledCorpusShard, ell_capacity

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class LDAConfig:
    num_topics: int = 1024
    alpha: float | None = None       # default 50/K (paper §2.1)
    beta: float = 0.01
    tile_tokens: int = 256           # tokens per word tile (C6)
    tiles_per_step: int = 64         # vmap width inside the sweep scan
    ell_capacity: int | None = None  # P; None = exact bound from corpus
    micro_chunks: int = 1            # M: 1 = WorkSchedule1, >1 = WorkSchedule2
    sampler: str = "sq"              # "sq" (paper) | "pallas" (fused kernel)
    #                                  | "dense" (O(K) baseline)
    topic_dtype: Any = jnp.int16     # C7
    compressed_sync: bool = False    # int16 delta all-reduce (see sync.py)
    sync_overlap: bool = False       # WS2: sync each micro-chunk's phi_delta
    #                                  immediately so the collective overlaps
    #                                  the next chunk's sampling (exact: psum
    #                                  is linear over int).  No-op when
    #                                  micro_chunks == 1.
    seed: int = 0

    def __post_init__(self):
        if self.sampler not in ("sq", "pallas", "dense"):
            raise ValueError(f"unknown sampler {self.sampler!r}")
        # C7 only compresses what fits: init_state/sampler store topic ids
        # as topic_dtype, so K - 1 must be representable or z wraps silently.
        try:
            max_topic = int(jnp.iinfo(self.topic_dtype).max)
        except ValueError as e:
            raise ValueError(
                f"topic_dtype must be an integer dtype, got "
                f"{self.topic_dtype!r}") from e
        if self.num_topics - 1 > max_topic:
            raise ValueError(
                f"num_topics={self.num_topics} does not fit "
                f"topic_dtype={jnp.dtype(self.topic_dtype).name} (max topic "
                f"id {max_topic}); pass topic_dtype=jnp.int32")

    def resolved_alpha(self) -> float:
        return 50.0 / self.num_topics if self.alpha is None else self.alpha


def resolve_config(cfg: LDAConfig, corpus: Corpus) -> LDAConfig:
    """The one place defaults derived from the corpus get filled in.

    Every driver (``repro.train.fit`` single-host and mesh alike) resolves
    its config exactly once through here and threads the SAME object
    everywhere afterwards — the resolved config is what ``TrainResult.cfg``
    surfaces for reproducibility.  Idempotent."""
    _check_count_field(cfg, int(corpus.doc_lengths().max(initial=0)))
    if cfg.ell_capacity is None:
        cfg = dataclasses.replace(
            cfg, ell_capacity=ell_capacity(corpus, cfg.num_topics))
    return cfg


def _check_count_field(cfg: LDAConfig, longest_doc: int) -> None:
    """Under ``sampler="pallas"``, refuse (``ValueError``) a corpus whose
    longest document does not fit the count field of the kernel's packed
    ELL words; the other samplers keep counts and topics apart."""
    if cfg.sampler == "pallas":
        from ..kernels.lda_sample import kernel as lda_kernel
        lda_kernel.check_count_field(longest_doc, cfg.num_topics)


class LDAState(NamedTuple):
    z: Array          # (n, t) topic assignments
    phi_vk: Array     # (V_local, K)
    phi_sum: Array    # (K,)
    iteration: Array  # ()


class IterStats(NamedTuple):
    sparse_frac: Array
    ell_overflow: Array  # docs exceeding ELL capacity (0 in exact mode)
    mean_s_over_sq: Array  # mean S/(S+Q) sparse mass share (sq sampler only)
    row_width_share: Array  # mean sampled ELL lanes / padded width (pallas)


def state_from_z(
    cfg: LDAConfig,
    shard: TiledCorpusShard,
    z: Array,
    iteration,
    data_axes=None,
    model_axes=None,
) -> LDAState:
    """Rebuild the derived counts from assignments (init, restore, elastic)."""
    phi_local = updates.phi_from_z(z, shard.tile_word, shard.token_mask,
                                   shard.num_words, cfg.num_topics)
    phi = sync.sync_phi(phi_local, data_axes)
    phi_sum = sync.global_phi_sum(phi, model_axes)
    return LDAState(z=z, phi_vk=phi, phi_sum=phi_sum,
                    iteration=jnp.asarray(iteration, jnp.int32))


def init_state(
    cfg: LDAConfig,
    shard: TiledCorpusShard,
    key: Array,
    data_axes=None,
    model_axes=None,
) -> LDAState:
    K = cfg.num_topics
    n, t = shard.token_doc.shape
    z0 = jax.random.randint(key, (n, t), 0, K, jnp.int32).astype(cfg.topic_dtype)
    return state_from_z(cfg, shard, z0, 0, data_axes, model_axes)


def _build_theta_ell(cfg: LDAConfig, shard: TiledCorpusShard, z, model_axes):
    K = cfg.num_topics
    # nested under ``lda.plan``: the theta rebuild (scatter and sync) and
    # the ELL top-k are timed apart in a device profile
    with jax.named_scope("theta"):
        theta = updates.theta_from_z(z, shard.token_doc, shard.token_mask,
                                     shard.num_docs_local, K)
        theta = sync.sync_theta(theta, model_axes)
    P = cfg.ell_capacity
    if P is None:
        longest = int(shard.doc_length.max(initial=0))
        _check_count_field(cfg, longest)
        P = min(K, longest) if shard.doc_length.size else K
    with jax.named_scope("ell"):
        counts, topics, overflow = updates.theta_to_ell(theta, min(P, K))
    return theta, counts, topics, overflow


def lda_iteration(
    cfg: LDAConfig,
    shard: TiledCorpusShard,
    state: LDAState,
    base_key: Array,
    data_axes=None,
    model_axes=None,
    heavy_rows=None,   # (H,) int32 — int32-sync rows under compressed_sync
) -> tuple[LDAState, IterStats]:
    """One full sweep over this shard's tokens + phi sync.

    ``cfg.sync_overlap`` (WorkSchedule2 only) moves the phi_delta all-reduce
    inside the micro-chunk loop: each chunk's delta is synced as soon as it
    exists, so the collective overlaps the next chunk's sampling instead of
    serializing after the sweep.  Exact by linearity of psum over int — the
    accumulated per-chunk syncs equal the one-shot sync bit for bit (the
    compressed int16 path included; see ``sync.sync_phi_delta``).  Draws are
    untouched: keys never depend on the sync schedule.
    """
    K = cfg.num_topics
    alpha, beta = cfg.resolved_alpha(), cfg.beta
    key = jax.random.fold_in(base_key, state.iteration)
    for ax in (tuple(data_axes or ()) + tuple(model_axes or ())):
        key = jax.random.fold_in(key, jax.lax.axis_index(ax))

    # jax.named_scope phase names (plan / sample / phi_delta / sync) are pure
    # HLO metadata: they make device profiles line up with the host spans
    # repro.obs records, and cannot change draws.
    with jax.named_scope("lda.plan"):
        theta, ell_c, ell_t, overflow = _build_theta_ell(
            cfg, shard, state.z, model_axes)

    n, t = state.z.shape
    M = cfg.micro_chunks
    v_total = shard.num_words_total or shard.num_words
    sweep_kwargs = dict(alpha=alpha, beta=beta, num_words_total=v_total)
    interpret = resolve_interpret()

    if M == 1:  # WorkSchedule1: whole shard resident, one sweep
        if cfg.sampler == "sq":
            with jax.named_scope("lda.sample"):
                z_new, stats = sampler.sample_sweep(
                    state.phi_vk, state.phi_sum, shard.tile_word,
                    shard.token_doc, shard.token_mask, state.z, ell_c, ell_t,
                    key, tiles_per_step=min(cfg.tiles_per_step, n),
                    **sweep_kwargs)
            sparse_frac = stats.sparse_frac
            mean_ssq = stats.mean_s_over_sq
            width_share = stats.row_width_share
        elif cfg.sampler == "pallas":
            from ..kernels.lda_sample import ops as lda_kernel
            with jax.named_scope("lda.sample"):
                z_new, stats = lda_kernel.lda_sample(
                    shard.tile_word, shard.token_doc, shard.token_mask,
                    state.z, state.phi_vk, state.phi_sum, ell_c, ell_t, key,
                    impl="pallas", interpret=interpret, **sweep_kwargs)
            sparse_frac = stats.sparse_frac
            mean_ssq = stats.mean_s_over_sq
            width_share = stats.row_width_share
        else:
            with jax.named_scope("lda.sample"):
                z_new = dense_sampler.sample_sweep_dense(
                    state.phi_vk, state.phi_sum, shard.tile_word,
                    shard.token_doc, shard.token_mask, state.z, theta, key,
                    tiles_per_step=min(cfg.tiles_per_step, n), **sweep_kwargs)
            sparse_frac = jnp.float32(0)
            mean_ssq = jnp.float32(0)
            width_share = jnp.float32(1)
    else:  # WorkSchedule2: M micro-chunks, theta refreshed between chunks
        n_pad = -n % M
        tw_a, td_a, tm_a, z_a = shard.tile_word, shard.token_doc, shard.token_mask, state.z
        if n_pad:  # masked-out padding tiles (static at trace time)
            tw_a = jnp.concatenate([tw_a, jnp.zeros(n_pad, tw_a.dtype)])
            td_a = jnp.concatenate([td_a, jnp.zeros((n_pad, t), td_a.dtype)])
            tm_a = jnp.concatenate([tm_a, jnp.zeros((n_pad, t), bool)])
            z_a = jnp.concatenate([z_a, jnp.zeros((n_pad, t), z_a.dtype)])
        nc = (n + n_pad) // M
        P = ell_c.shape[1]
        # sync_overlap: sync each chunk's phi_delta as soon as it exists —
        # the all-reduce overlaps the next chunk's sampling (which reads
        # only the frozen iteration-start phi, never the in-flight sum)
        overlap = cfg.sync_overlap and M > 1

        if cfg.sampler == "pallas":
            # unrolled over the M micro-chunks (M is small and static):
            # unrolling produces the exact op sequence of the "sq" scan
            # below, so draws stay bit-identical.  theta (and the ELL
            # re-slice from it) is carried incrementally — theta_delta,
            # never a rebuild.
            from ..kernels.lda_sample import ops as lda_kernel
            keys_m = jax.random.split(key, M)
            theta_c = theta
            phi_acc = jnp.zeros_like(state.phi_vk) if overlap else None
            z_parts, sfs_l, ssqs_l, ws_l = [], [], [], []
            for m in range(M):
                sl = slice(m * nc, (m + 1) * nc)
                cnts, tpcs = jax.lax.top_k(theta_c, P)
                with jax.named_scope("lda.sample"):
                    z_c, st = lda_kernel.lda_sample(
                        tw_a[sl], td_a[sl], tm_a[sl], z_a[sl],
                        state.phi_vk, state.phi_sum, cnts, tpcs, keys_m[m],
                        impl="pallas", interpret=interpret, **sweep_kwargs)
                delta = updates.theta_delta(z_a[sl], z_c, td_a[sl], tm_a[sl],
                                            theta_c.shape[0], K)
                theta_c = theta_c + sync.sync_theta(delta, model_axes)
                if overlap:
                    with jax.named_scope("lda.phi_delta"):
                        d_c = updates.phi_delta(z_a[sl], z_c, tw_a[sl],
                                                tm_a[sl], shard.num_words, K)
                    with jax.named_scope("lda.sync"):
                        phi_acc = phi_acc + sync.sync_phi_delta(
                            d_c, data_axes, heavy_rows, cfg.compressed_sync)
                z_parts.append(z_c)
                sfs_l.append(st.sparse_frac)
                ssqs_l.append(st.mean_s_over_sq)
                ws_l.append(st.row_width_share)
            z_new = jnp.concatenate(z_parts)[:n]
            sparse_frac = jnp.stack(sfs_l).mean()
            mean_ssq = jnp.stack(ssqs_l).mean()
            width_share = jnp.stack(ws_l).mean()
        else:
            def chunk_step(carry, inp):
                theta_c, phi_acc = carry if overlap else (carry, None)
                tw, td, tm, zc, kc = inp
                cnts, tpcs = jax.lax.top_k(theta_c, P)
                if cfg.sampler == "sq":
                    z_c, st = sampler.sample_sweep(
                        state.phi_vk, state.phi_sum, tw, td, tm, zc, cnts, tpcs,
                        kc, tiles_per_step=min(cfg.tiles_per_step, nc), **sweep_kwargs)
                    sf, ssq = st.sparse_frac, st.mean_s_over_sq
                else:
                    z_c = dense_sampler.sample_sweep_dense(
                        state.phi_vk, state.phi_sum, tw, td, tm, zc, theta_c, kc,
                        tiles_per_step=min(cfg.tiles_per_step, nc), **sweep_kwargs)
                    sf, ssq = jnp.float32(0), jnp.float32(0)
                delta = updates.theta_delta(zc, z_c, td, tm,
                                            theta_c.shape[0], K)
                theta_n = theta_c + sync.sync_theta(delta, model_axes)
                if overlap:
                    d_c = updates.phi_delta(zc, z_c, tw, tm,
                                            shard.num_words, K)
                    phi_acc = phi_acc + sync.sync_phi_delta(
                        d_c, data_axes, heavy_rows, cfg.compressed_sync)
                    return (theta_n, phi_acc), (z_c, sf, ssq)
                return theta_n, (z_c, sf, ssq)

            xs = (
                tw_a.reshape(M, nc),
                td_a.reshape(M, nc, t),
                tm_a.reshape(M, nc, t),
                z_a.reshape(M, nc, t),
                jax.random.split(key, M),
            )
            carry0 = ((theta, jnp.zeros_like(state.phi_vk)) if overlap
                      else theta)
            with jax.named_scope("lda.sample"):
                last, (z_chunks, sfs, ssqs) = jax.lax.scan(
                    chunk_step, carry0, xs)
            phi_acc = last[1] if overlap else None
            z_new = z_chunks.reshape(n + n_pad, t)[:n]
            sparse_frac = sfs.mean()
            mean_ssq = ssqs.mean()
            width_share = jnp.float32(1)

    if M > 1 and cfg.sync_overlap:
        # the per-chunk syncs above already hold the whole iteration's
        # reduced delta: psum is linear over int32, so the accumulated sum
        # is bit-identical to the one-shot sync below (the per-chunk
        # scatter deltas are exact ints, compressed path included)
        with jax.named_scope("lda.sync"):
            phi = state.phi_vk + phi_acc
            phi_sum = sync.global_phi_sum(phi, model_axes)
        new_state = LDAState(z=z_new, phi_vk=phi, phi_sum=phi_sum,
                             iteration=state.iteration + 1)
        return new_state, IterStats(sparse_frac=sparse_frac,
                                    ell_overflow=overflow.sum(),
                                    mean_s_over_sq=mean_ssq,
                                    row_width_share=width_share)

    # incremental phi advance + reduce/broadcast (C3): one scatter/MXU pass
    # over the sweep's moves instead of a full count rebuild (and instead of
    # the TWO rebuilds the compressed_sync branch used to pay); exact in int
    # arithmetic, phi_old + delta == rebuild(z_new).
    with jax.named_scope("lda.phi_delta"):
        if cfg.sampler == "pallas":
            from ..kernels.phi_update import ops as phi_kernel
            delta = phi_kernel.phi_delta(
                shard.tile_word, shard.tile_first, state.z, z_new,
                shard.token_mask, num_words=shard.num_words, num_topics=K,
                impl="pallas", interpret=interpret)
        else:
            delta = updates.phi_delta(state.z, z_new, shard.tile_word,
                                      shard.token_mask, shard.num_words, K)
    with jax.named_scope("lda.sync"):
        # beyond-paper wire format: compressed_sync all-reduces the int16
        # per-iteration DELTA instead of rebuilt int32 counts — half the
        # bytes (C7 on the wire), exact for the long tail; rows whose corpus
        # flux can exceed int16 ride in heavy_rows and get an int32
        # correction (see sync.compressed_sync_phi / heavy_word_rows).
        phi = state.phi_vk + sync.sync_phi_delta(delta, data_axes,
                                                 heavy_rows,
                                                 cfg.compressed_sync)
        phi_sum = sync.global_phi_sum(phi, model_axes)
    new_state = LDAState(z=z_new, phi_vk=phi, phi_sum=phi_sum,
                         iteration=state.iteration + 1)
    return new_state, IterStats(sparse_frac=sparse_frac,
                                ell_overflow=overflow.sum(),
                                mean_s_over_sq=mean_ssq,
                                row_width_share=width_share)


def log_likelihood(
    cfg: LDAConfig, shard: TiledCorpusShard, state: LDAState,
    data_axes=None, model_axes=None,
) -> Array:
    """Joint collapsed LL (Fig. 8 metric).  In SPMD contexts: doc term psums
    over the doc shards; word term is computed from the phi this device holds
    (full replica in 1D; V-shard psum'd over model in 2D)."""
    alpha, beta = cfg.resolved_alpha(), cfg.beta
    theta = updates.theta_from_z(state.z, shard.token_doc, shard.token_mask,
                                 shard.num_docs_local, cfg.num_topics)
    theta = sync.sync_theta(theta, model_axes)
    dterm = likelihood.doc_term(theta, shard.doc_length, alpha)
    dterm = sync.maybe_psum(dterm, data_axes)
    winner = likelihood.word_inner_term(state.phi_vk, beta)
    winner = sync.maybe_psum(winner, model_axes)
    wouter = likelihood.word_outer_term(state.phi_sum, beta,
                                        shard.num_words_total or shard.num_words)
    return dterm + winner + wouter


# ---------------------------------------------------------------------------
# TrainResult is the one result type every driver returns; the unified
# entry point is repro.train.fit (single-host AND mesh).  ``train`` below is
# a deprecated alias kept for old call sites.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainResult:
    state: LDAState
    ll_per_token: list[float]
    tokens_per_sec: list[float]
    # (sparse_frac, ell_overflow, S/(S+Q), row_width_share)
    stats: list[tuple[float, float, float, float]]
    compile_sec: float = 0.0  # jit compile time, excluded from tokens_per_sec
    cfg: LDAConfig | None = None  # the resolved config actually trained with


def train(
    corpus: Corpus,
    cfg: LDAConfig,
    num_iterations: int,
    eval_every: int = 1,
    shard: TiledCorpusShard | None = None,
    callback: Callable[[int, LDAState, float], None] | None = None,
    obs=None,                      # repro.obs.Observability
    metrics_out: str | None = None,  # per-iteration JSONL sink path
    sanitize: bool = False,        # transfer-guard the sampling hot path
) -> TrainResult:
    """Deprecated alias for ``repro.train.fit`` (single-host path)."""
    import warnings

    warnings.warn(
        "trainer.train is deprecated; use repro.train.fit(corpus, cfg, "
        "num_iterations, ...) — same behaviour, one entry point for "
        "single-host and mesh training", DeprecationWarning, stacklevel=2)
    from repro.train import fit

    return fit(corpus, cfg, num_iterations, eval_every=eval_every,
               shard=shard, callback=callback, obs=obs,
               metrics_out=metrics_out, sanitize=sanitize)
