"""Fold-in Gibbs inference for unseen documents (the serving hot path).

Given a *frozen* topic-word model (phi_vk, phi_sum) from a snapshot, estimate
the doc-topic mixture theta of documents the model never trained on: assign
random topics, then run delayed-count Gibbs sweeps where only the document
side moves — phi stays fixed, exactly the paper's delayed-count semantics
applied across the train/serve boundary.

The per-token distribution is the training sampler's Eq. 1 with frozen phi:

    p(z = k | w, d) ∝ (theta_dk + alpha) * p*_w(k)
                    =  theta_dk * p*_w(k)  +  alpha * p*_w(k)
                       `-- p1: sparse -----'  `-- p2: dense --'

and we keep the C4 S/Q split in inference: theta of a fresh doc has at most
min(L, K) non-zero topics, so S is evaluated over an ELL top-P slice while
the dense side reuses the two-level blocked search (C5).  p*_w(k) is gathered
once per request token (C7 sub-expression reuse across every sweep).

Shapes are static per (B, L) so the jit cache is keyed only by the engine's
shape buckets; phi enters as an argument, so hot-swapping a same-shape
snapshot never recompiles.  Working set is O(B*L*K) floats — the engine's
buckets bound it.

Interchangeable implementations behind ``impl`` (all draw-identical given
the same key — same split tree, same uniforms):

* ``"xla"`` — the pure-jnp sweeps of
  ``repro.kernels.fold_in.ref`` (re-materializes the per-sweep
  intermediates each sweep);
* ``"pallas"`` — ``repro.kernels.fold_in``: one grid step per doc, theta
  counts + gathered p* rows + the S/Q search tables stay on-chip across all
  sweeps (compiled on TPU, interpret mode elsewhere).

Everything downstream of the per-token gather consumes only the gathered
``(B, L, K)`` phi rows (``_fold_in_rows``), never the full ``(V, K)`` phi.
That factoring is what makes **V-sharded serving** possible: for a
``ShardedModelSnapshot`` the gather runs inside ``shard_map`` under one of
two comm strategies (``InferConfig.comm``): ``"psum"`` — each device
gathers the rows of the word ids *its* phi block owns (zeros elsewhere) and
a ``psum`` over the shard axis assembles the exact int32 rows — or
``"all2all"`` — request-side token routing, where each shard sweeps only a
contiguous doc slice and moves just the routed token ids + their rows over
the mesh (see the V-sharded section below).  Either way the sweep code
(XLA scan or the Pallas kernel, which only ever sees the gathered rows)
produces draws bit-identical to the single-device path under the same key.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.fold_in import ops as foldin_ops

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class InferConfig:
    """Fold-in schedule: ``burn_in`` discarded sweeps, then ``samples``
    sweeps whose thetas are averaged (posterior-mean estimate)."""

    burn_in: int = 8
    samples: int = 4
    top_k: int = 8
    ell_capacity: int | None = None  # P; None -> min(L, K)
    impl: str = "xla"                # "xla" | "pallas"
    # How a V-sharded snapshot assembles the per-token phi rows:
    #   "psum"    — every shard gathers its owned rows at full (B, L, K) and
    #               a psum assembles them (comm volume B*L*K per device);
    #   "all2all" — request-side token routing: each shard sweeps a doc
    #               slice, routes only its real tokens' ids to the owning
    #               shards and gets the (n_tok, K) rows back via all_to_all
    #               (comm scales with tokens routed, not B*L*K);
    #   "auto"    — defer to the snapshot's own ``comm`` tag.
    # Draws are bit-identical across all strategies (and to the dense path).
    comm: str = "auto"               # "auto" | "psum" | "all2all"


class FoldInResult(NamedTuple):
    theta: Array        # (B, K) float32 — normalized posterior-mean mixture
    top_topics: Array   # (B, top_k) int32 — heaviest topics per doc
    top_weights: Array  # (B, top_k) float32 — their theta mass
    sparse_frac: Array  # () — fraction of draws taken on the sparse S side
    mean_s_over_sq: Array  # () — mean S/(S+Q) over real tokens


def _fold_in_rows(
    phi_tok: Array,     # (B, L, K) int32 — gathered phi rows, one per token
    phi_sum: Array,     # (K,) int32 — frozen per-topic totals
    mask: Array,        # (B, L) bool — False on padding slots
    key: Array,
    alpha,              # traced scalars: a snapshot with different
    beta,               # hyperparams hot-swaps without recompiling
    *,
    num_words_total: int,
    burn_in: int,
    samples: int,
    top_k: int,
    ell_capacity: int | None,
    impl: str,
    interpret: bool | None,
) -> FoldInResult:
    """The fold-in sweeps, downstream of the per-token phi gather.

    Partition-agnostic: ``phi_tok`` may come from a single-device
    ``phi_vk[tokens]`` or from a sharded local-gather + psum — the draws are
    identical either way (int32 rows are exact under psum).
    """
    B, L = mask.shape
    K = phi_sum.shape[0]
    P = min(ell_capacity or L, L, K)
    kk = min(top_k, K)
    n_real = jnp.maximum(mask.sum(), 1).astype(jnp.float32)
    denom = n_real * samples

    with jax.named_scope("serve.sweeps"):
        z0, uniforms = foldin_ops.draw_fold_in_randoms(
            key, B, L, K, burn_in + samples)
        # "xla": the pure-jnp sweeps; "pallas": all sweeps fused on-chip
        # (repro.kernels.fold_in) — draw-identical, per-doc partials back
        tsum, sps, ssqs = foldin_ops.fold_in_sweeps_drawn(
            phi_tok, phi_sum, mask, z0, uniforms, alpha, beta,
            num_words_total=num_words_total, burn_in=burn_in,
            samples=samples, ell_capacity=P, impl=impl,
            interpret=resolve_interpret(interpret))
    with jax.named_scope("serve.assemble"):
        return _assemble(tsum, sps.sum(), ssqs.sum(), alpha, samples, kk,
                         denom)


_STATICS = ("num_words_total", "burn_in", "samples", "top_k", "ell_capacity",
            "impl", "interpret")


@functools.partial(jax.jit, static_argnames=_STATICS)
def fold_in(
    phi_vk: Array,      # (V, K) int32 — frozen topic-word counts
    phi_sum: Array,     # (K,) int32 — frozen per-topic totals
    tokens: Array,      # (B, L) int32 word ids (anything under mask=False ok)
    mask: Array,        # (B, L) bool — False on padding slots
    key: Array,
    alpha,
    beta,
    *,
    num_words_total: int,
    burn_in: int = 8,
    samples: int = 4,
    top_k: int = 8,
    ell_capacity: int | None = None,
    impl: str = "xla",
    interpret: bool | None = None,
) -> FoldInResult:
    """Estimate theta for a batch of unseen documents against frozen phi.

    ``interpret=None`` resolves by backend: the Pallas kernel compiles on
    TPU and falls back to the interpreter everywhere else.
    """
    with jax.named_scope("serve.gather"):
        phi_tok = phi_vk[tokens]
    return _fold_in_rows(
        phi_tok, phi_sum, mask, key, alpha, beta,
        num_words_total=num_words_total, burn_in=burn_in, samples=samples,
        top_k=top_k, ell_capacity=ell_capacity, impl=impl,
        interpret=interpret)


def _assemble(theta_sum, sp_total, ssq_total, alpha, samples: int, kk: int,
              denom) -> FoldInResult:
    """Sweep partials -> FoldInResult; shared by every impl so the contract
    (posterior-mean smoothing, normalization, top-k) cannot diverge."""
    theta_mean = theta_sum.astype(jnp.float32) / samples + alpha   # (B, K)
    theta_mean = theta_mean / theta_mean.sum(-1, keepdims=True)
    tw, tt = jax.lax.top_k(theta_mean, kk)
    return FoldInResult(
        theta=theta_mean,
        top_topics=tt.astype(jnp.int32),
        top_weights=tw,
        sparse_frac=sp_total / denom,
        mean_s_over_sq=ssq_total / denom,
    )


# ---------------------------------------------------------------------------
# packed request buffer: ONE host->device transfer per engine batch
# ---------------------------------------------------------------------------
# The engine used to ship tokens + mask (+ a host-built PRNG key) as separate
# arrays; every jit call committed each one to the device.  The packed
# buffer fuses the whole request batch into a single pinned int32 array:
#
#     row i < B :  [tok_0, ..., tok_{L-1}, doc_length_i]
#     row B     :  [batch_seed, 0, ...]
#
# so exactly one H2D transfer carries a batch, and mask/key are derived on
# device (mask = iota < length; key = jax.random.key(seed) — identical to
# the key the engine used to build on the host from the same seed int).


def pack_request_buffer(docs: Sequence[np.ndarray], batch: int, length: int,
                        seed: int) -> np.ndarray:
    """Per-doc word-id arrays -> one (batch+1, length+1) int32 buffer."""
    buf = np.zeros((batch + 1, length + 1), np.int32)
    for i, d in enumerate(docs):
        d = np.asarray(d, np.int32)[:length]
        buf[i, : len(d)] = d
        buf[i, length] = len(d)
    buf[batch, 0] = seed
    return buf


def _unpack_request_buffer(buf: Array):
    """(B+1, L+1) device buffer -> tokens (B, L), mask (B, L), key."""
    B, L = buf.shape[0] - 1, buf.shape[1] - 1
    tokens = buf[:-1, :L]
    lengths = buf[:-1, L]
    mask = jax.lax.broadcasted_iota(jnp.int32, (B, L), 1) < lengths[:, None]
    key = jax.random.key(buf[-1, 0])
    return tokens, mask, key


@functools.partial(jax.jit, static_argnames=_STATICS)
def fold_in_buffer(
    phi_vk: Array,      # (V, K) int32
    phi_sum: Array,     # (K,) int32
    buf: Array,         # (B+1, L+1) int32 packed request buffer (on device)
    hyper: Array,       # (2,) float32 — [alpha, beta], staged once per snapshot
    *,
    num_words_total: int,
    burn_in: int = 8,
    samples: int = 4,
    top_k: int = 8,
    ell_capacity: int | None = None,
    impl: str = "xla",
    interpret: bool | None = None,
) -> FoldInResult:
    """``fold_in`` over a packed request buffer (the engine's batch unit).

    The ``jax.named_scope`` names here (and in the sweep path) are pure HLO
    metadata — they line device profiles up with the host phase spans the
    engine records, and cannot change draws."""
    with jax.named_scope("serve.unpack"):
        tokens, mask, key = _unpack_request_buffer(buf)
    with jax.named_scope("serve.gather"):
        phi_tok = phi_vk[tokens]
    return _fold_in_rows(
        phi_tok, phi_sum, mask, key, hyper[0], hyper[1],
        num_words_total=num_words_total, burn_in=burn_in, samples=samples,
        top_k=top_k, ell_capacity=ell_capacity, impl=impl,
        interpret=interpret)


# ---------------------------------------------------------------------------
# V-sharded fold-in: phi partitioned over a mesh axis
# ---------------------------------------------------------------------------
# Two comm strategies assemble the per-token phi rows (InferConfig.comm):
#
# * "psum"    — every shard gathers the rows of the word ids its block owns
#   (zeros elsewhere) at full (B, L, K) and a psum over the shard axis
#   assembles the exact int32 rows; the sweeps then run replicated.  Simple,
#   but the psum moves B*L*K int32 per device however few tokens the batch
#   really holds.
#
# * "all2all" — request-side token routing.  Each shard takes a contiguous
#   slice of the batch's docs, buckets its *real* tokens' local-row ids by
#   owning shard (``route_buckets``), all_to_all's the id lists, the owners
#   local-gather their phi rows, and a second all_to_all returns the
#   (n_tok, K) rows into batch order.  The sweeps then run on the doc slice
#   only (randoms drawn full-shape and sliced, so draws stay bit-identical),
#   and per-doc partials are all_gather'd.  Comm scales with tokens actually
#   routed — and the sweep compute is sharded S-ways for free.
#
# Both are bit-identical to the dense path under the same key for every impl.

_SHARDED_JITS: list = []   # every built sharded jit, for cache-size probes


@functools.lru_cache(maxsize=None)
def _sharded_fold_in_fns(mesh, axis: str, num_words_total: int, burn_in: int,
                         samples: int, top_k: int, ell_capacity: int | None,
                         impl: str, interpret: bool | None,
                         comm: str = "psum", capacity: int | None = None):
    """Build (and cache per mesh + schedule + comm strategy) the shard_map'd
    fold-in.

    Layout inside the map: each device holds one (Vs, K) phi block plus the
    replicated (V,) word->shard / word->local-row maps; tokens, mask, key
    and hyperparams are replicated.  ``comm`` picks the row-assembly
    strategy (see module section comment); ``capacity`` is the all2all
    plan's static per-(requester, owner) bucket size and is part of the
    cache key (power-of-two bucketed by the plan, so recompiles stay
    bounded).

    Returns ``(run_tokens, run_buffer)`` jitted entry points; both
    strategies are draw-identical to the single-device path under the same
    key.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.partition import (doc_slice_bounds,
                                             doc_slice_owner, route_buckets)

    kw = dict(num_words_total=num_words_total, burn_in=burn_in,
              samples=samples, top_k=top_k, ell_capacity=ell_capacity,
              impl=impl, interpret=interpret)
    repl = P()
    num_shards = int(mesh.shape[axis])

    def inner_psum(phi_blk, phi_sum, shard_of, local_id, tokens, mask,
                   key_data, hyper):
        s = jax.lax.axis_index(axis)
        tok_shard = shard_of[tokens]                       # (B, L)
        mine = tok_shard == s
        rows = phi_blk[0][jnp.where(mine, local_id[tokens], 0)]
        rows = jnp.where(mine[..., None], rows, 0)         # foreign words: 0
        phi_tok = jax.lax.psum(rows, axis)                 # exact int32 rows
        key = jax.random.wrap_key_data(key_data)
        return _fold_in_rows(phi_tok, phi_sum, mask, key, hyper[0], hyper[1],
                             **kw)

    def inner_a2a(phi_blk, phi_sum, shard_of, local_id, tokens, mask,
                  key_data, hyper):
        S = num_shards
        B, L = tokens.shape
        K = phi_sum.shape[0]
        # slice policy + overlap-dedup map as trace-time constants, from the
        # one place that owns them (distributed.partition)
        starts_np, Bs = doc_slice_bounds(B, S)
        own_np, row_np = doc_slice_owner(B, S)
        T = Bs * L
        s = jax.lax.axis_index(axis)
        start = jnp.asarray(starts_np)[s]

        # --- route: ids out, rows back -----------------------------------
        tok_s = jax.lax.dynamic_slice_in_dim(tokens, start, Bs, 0)
        msk_s = jax.lax.dynamic_slice_in_dim(mask, start, Bs, 0)
        flat_tok = tok_s.reshape(T)
        owner = jnp.where(msk_s.reshape(T), shard_of[flat_tok],
                          S).astype(jnp.int32)             # padding: nowhere
        send_ids, src = route_buckets(owner, local_id[flat_tok], S, capacity)
        recv_ids = jax.lax.all_to_all(send_ids, axis, 0, 0)   # requests in
        rows = phi_blk[0][recv_ids]                 # (S, C, K) local gather
        rows_back = jax.lax.all_to_all(rows, axis, 0, 0)      # rows home
        phi_tok_s = jnp.zeros((T, K), jnp.int32).at[src.reshape(-1)].set(
            rows_back.reshape(-1, K), mode="drop").reshape(Bs, L, K)

        # --- sweep the doc slice (full-shape randoms, sliced) ------------
        key = jax.random.wrap_key_data(key_data)
        z0, uniforms = foldin_ops.draw_fold_in_randoms(
            key, B, L, K, burn_in + samples)
        z0_s = jax.lax.dynamic_slice_in_dim(z0, start, Bs, 0)
        uni_s = jax.lax.dynamic_slice_in_dim(uniforms, start, Bs, 1)
        P_ell = min(ell_capacity or L, L, K)
        tsum, sp, ssq = foldin_ops.fold_in_sweeps_drawn(
            phi_tok_s, phi_sum, msk_s, z0_s, uni_s, hyper[0], hyper[1],
            num_words_total=num_words_total, burn_in=burn_in,
            samples=samples, ell_capacity=P_ell, impl=impl,
            interpret=resolve_interpret(interpret))

        # --- assemble: per-doc partials home, overlap deduplicated -------
        g_t = jax.lax.all_gather(tsum, axis)               # (S, Bs, K)
        g_sp = jax.lax.all_gather(sp, axis)                # (S, Bs)
        g_ssq = jax.lax.all_gather(ssq, axis)
        own, row = jnp.asarray(own_np), jnp.asarray(row_np)
        n_real = jnp.maximum(mask.sum(), 1).astype(jnp.float32)
        return _assemble(g_t[own, row], g_sp[own, row].sum(),
                         g_ssq[own, row].sum(), hyper[0], samples,
                         min(top_k, K), n_real * samples)

    inner = inner_a2a if comm == "all2all" else inner_psum
    mapped = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(P(axis), repl, repl, repl, repl, repl, repl, repl),
        out_specs=FoldInResult(repl, repl, repl, repl, repl),
        check_vma=False)

    def run_tokens(phi_blocks, phi_sum, shard_of, local_id, tokens, mask,
                   key, hyper):
        return mapped(phi_blocks, phi_sum, shard_of, local_id, tokens,
                      mask.astype(bool), jax.random.key_data(key), hyper)

    def run_buffer(phi_blocks, phi_sum, shard_of, local_id, buf, hyper):
        tokens, mask, key = _unpack_request_buffer(buf)
        return mapped(phi_blocks, phi_sum, shard_of, local_id, tokens, mask,
                      jax.random.key_data(key), hyper)

    fns = (jax.jit(run_tokens), jax.jit(run_buffer))
    _SHARDED_JITS.extend(fns)
    return fns


def resolve_comm(snap, cfg: InferConfig) -> str:
    """Effective comm strategy: the config's, or — on ``"auto"`` — the
    snapshot's own ``comm`` tag (how "strategy per snapshot" is selected)."""
    comm = cfg.comm
    if comm in (None, "auto"):
        comm = getattr(snap, "comm", "psum")
    if comm not in ("psum", "all2all"):
        raise ValueError(f"unknown comm strategy {comm!r} "
                         "(expected 'psum', 'all2all' or 'auto')")
    return comm


def routing_plan(snap, tokens, mask):
    """Host-side all2all routing plan for one batch against a sharded
    snapshot: the static bucket capacity plus this batch's measured
    bytes-moved under both comm strategies."""
    from repro.distributed.partition import plan_token_routing

    return plan_token_routing(snap.host_word_shard_of, np.asarray(tokens),
                              np.asarray(mask), snap.num_shards,
                              snap.num_topics)


def _sharded_statics(snap, cfg: InferConfig, interpret: bool | None,
                     comm: str = "psum", capacity: int | None = None):
    return (snap.mesh, snap.axis, snap.num_words_total, cfg.burn_in,
            cfg.samples, cfg.top_k, cfg.ell_capacity, cfg.impl, interpret,
            comm, capacity)


def fold_in_sharded(snap, tokens, mask, key, cfg: InferConfig,
                    interpret: bool | None = None,
                    capacity: int | None = None) -> FoldInResult:
    """Fold-in against a ``ShardedModelSnapshot`` (explicit tokens + key).

    Under ``comm="all2all"`` the routing capacity is planned host-side from
    the batch unless the caller already did (``capacity``)."""
    comm = resolve_comm(snap, cfg)
    if comm == "all2all" and capacity is None:
        capacity = routing_plan(snap, tokens, mask).capacity
    run_tokens, _ = _sharded_fold_in_fns(
        *_sharded_statics(snap, cfg, interpret, comm,
                          capacity if comm == "all2all" else None))
    with snap.mesh:
        return run_tokens(snap.phi_blocks, snap.phi_sum, snap.word_shard_of,
                          snap.word_local_id, jnp.asarray(tokens, jnp.int32),
                          jnp.asarray(mask), key, snap.hyper)


def _host_batch_from_buffer(buf):
    """Packed request buffer -> host (tokens, mask) for routing plans."""
    b = np.asarray(buf)
    L = b.shape[1] - 1
    tokens, lengths = b[:-1, :L], b[:-1, L]
    return tokens, np.arange(L)[None, :] < lengths[:, None]


def fold_in_request(snap, buf, cfg: InferConfig,
                    interpret: bool | None = None,
                    capacity: int | None = None) -> FoldInResult:
    """One engine batch from a packed request buffer, against either a dense
    ``ModelSnapshot`` or a ``ShardedModelSnapshot`` (dispatch point).

    The engine plans the all2all capacity from its host-side copy of the
    batch and passes it in; other callers pay one D2H copy of the (small)
    buffer here."""
    from repro.serve.snapshot import ShardedModelSnapshot

    if isinstance(snap, ShardedModelSnapshot):
        comm = resolve_comm(snap, cfg)
        if comm == "all2all" and capacity is None:
            capacity = routing_plan(snap, *_host_batch_from_buffer(buf)
                                    ).capacity
        _, run_buffer = _sharded_fold_in_fns(
            *_sharded_statics(snap, cfg, interpret, comm,
                              capacity if comm == "all2all" else None))
        with snap.mesh:
            return run_buffer(snap.phi_blocks, snap.phi_sum,
                              snap.word_shard_of, snap.word_local_id, buf,
                              snap.hyper)
    return fold_in_buffer(
        snap.phi_vk, snap.phi_sum, buf, snap.hyper,
        num_words_total=snap.num_words_total, burn_in=cfg.burn_in,
        samples=cfg.samples, top_k=cfg.top_k, ell_capacity=cfg.ell_capacity,
        impl=cfg.impl, interpret=interpret)


def serve_cache_size() -> int:
    """Compiled-variant count across every serving entry point (the engine's
    bucketing invariant: a batch in a seen (B, L) bucket never recompiles)."""
    return (fold_in._cache_size() + fold_in_buffer._cache_size()
            + sum(f._cache_size() for f in _SHARDED_JITS))


def fold_in_cost(batch: int, length: int, cfg: InferConfig) -> float:
    """Relative execution-cost model of one fold-in batch: token-sweeps
    dominate, so cost ~ B * L * total sweeps (burn-in + samples + init).

    Dimensionless on purpose — the engine's SLO scheduler uses cost
    *ratios* to transfer a measured per-bucket execution time onto buckets
    it has not timed yet (never to predict absolute milliseconds)."""
    return float(max(batch, 1) * max(length, 1)
                 * (cfg.burn_in + cfg.samples + 1))


def fold_in_config(snapshot, tokens, mask, key, cfg: InferConfig) -> FoldInResult:
    """Convenience wrapper: run fold-in from a (dense or sharded) snapshot
    + InferConfig."""
    from repro.serve.snapshot import ShardedModelSnapshot

    if isinstance(snapshot, ShardedModelSnapshot):
        return fold_in_sharded(snapshot, tokens, mask, key, cfg)
    return fold_in(
        snapshot.phi_vk, snapshot.phi_sum, tokens, mask, key,
        snapshot.alpha, snapshot.beta,
        num_words_total=snapshot.num_words_total,
        burn_in=cfg.burn_in, samples=cfg.samples, top_k=cfg.top_k,
        ell_capacity=cfg.ell_capacity, impl=cfg.impl,
    )


def pack_docs(
    docs: Sequence[np.ndarray],
    length: int | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """List of per-doc word-id arrays -> padded (B, L) tokens + mask.

    Docs longer than ``length`` are truncated (serving contract: the engine's
    largest length bucket caps request size).
    """
    if length is None:
        length = max((len(d) for d in docs), default=1)
    B = len(docs)
    tokens = np.zeros((B, length), np.int32)
    mask = np.zeros((B, length), bool)
    for i, d in enumerate(docs):
        d = np.asarray(d, np.int32)[:length]
        tokens[i, : len(d)] = d
        mask[i, : len(d)] = True
    return tokens, mask
