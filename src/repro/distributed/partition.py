"""Multi-device LDA partitions on JAX meshes (paper §4-§5 + DESIGN.md §3).

Two partition modes:

* ``"1d"`` — paper-faithful partition-by-document: the corpus is split into
  one chunk per device over *all* the given doc axes (balanced by token
  count, C1); phi is fully replicated and reduce+broadcast (psum, C3) every
  iteration.  Matches CuLDA_CGS exactly; the phi all-reduce volume is
  K*V*4B per device per iteration.

* ``"2d"`` — beyond-paper doc x word hybrid: documents over ``doc_axes``,
  vocabulary over ``word_axes``.  Each device samples the tokens of
  (its docs) ∩ (its words) against its local phi rows; theta partials psum
  over the word axes, phi shards psum over the doc axes only — 1/|word axes|
  of the 1D collective volume.  The sampler itself is partition-agnostic
  (tiles carry local word ids).

Host-side construction is numpy: every shard is tiled once on the host,
stacked there with a leading shard axis, and placed on the mesh so that
each device receives only its own shard; ``jax.shard_map`` then finds the
corpus where its in_specs put it.
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import trainer as core_trainer
from repro.core.corpus import (
    Corpus, TiledCorpusShard, num_tiles, partition_by_document,
    tile_shard_host,
)

Array = jnp.ndarray


# array leaves that travel through shard_map (leading shard axis)
_CORPUS_FIELDS = ("tile_word", "token_doc", "token_mask", "tile_first",
                  "doc_length", "doc_global", "token_uid")

# A word's per-iteration phi_delta entry is bounded by its corpus frequency,
# so the int16 compressed sync (sync.compressed_sync_phi) is exact for every
# word occurring fewer than 2**15 times; words at or above the bound take the
# int32 correction path.
INT16_FLUX_BOUND = 1 << 15


def heavy_word_rows(corpus: Corpus, plan: "PartitionPlan") -> np.ndarray:
    """Per-device local phi rows too heavy for the int16 compressed sync.

    Rows of words with corpus frequency >= ``INT16_FLUX_BOUND`` can wrap the
    int16 delta all-reduce, so ``sync.compressed_sync_phi`` re-reduces just
    those rows in int32 and overwrites the wrapped entries with the exact
    sums.  Returns (num_devices, H) int32 in device (doc-major) order; rows
    are padded with row 0 — re-setting a row to its exact sum is a no-op, so
    padding never changes the result.
    """
    counts = np.bincount(corpus.word_ids, minlength=corpus.num_words)
    heavy = np.nonzero(counts >= INT16_FLUX_BOUND)[0].astype(np.int32)
    G = plan.num_doc_shards * plan.num_word_shards
    if plan.word_shard_of is None:      # 1d: phi is the full replicated V
        return np.tile(heavy, (G, 1))
    per = [np.sort(plan.word_local_id[heavy[plan.word_shard_of[heavy] == m]])
           for m in range(plan.num_word_shards)]
    H = max((p.size for p in per), default=0)
    rows = np.zeros((G, H), np.int32)
    for d in range(plan.num_doc_shards):
        for m in range(plan.num_word_shards):
            rows[d * plan.num_word_shards + m, : per[m].size] = per[m]
    return rows


# ---------------------------------------------------------------------------
# request-side token routing (V-sharded serving, comm="all2all")
# ---------------------------------------------------------------------------
# The V-sharded fold-in's original gather assembles the (B, L, K) int32 phi
# rows with a full psum — comm volume B*L*K per device regardless of how many
# tokens the batch actually holds.  Request-side routing moves only what the
# tokens need: each shard takes a contiguous slice of the batch's documents
# ("requester" role), buckets its real tokens' ids by owning shard (the same
# word->shard maps the LPT vocabulary partition builds), all_to_all's the
# (much smaller) id lists, the owners local-gather their phi rows, and a
# second all_to_all returns the (n_tok, K) rows into batch order.  The
# fold-in sweeps then run on each shard's doc slice only; per-doc results are
# all_gather'd at the end.  Comm scales with tokens routed, not B*L*K.


def doc_slice_bounds(num_docs: int, num_shards: int):
    """Contiguous per-shard document slices covering [0, num_docs).

    Every shard gets the same static slice width ``Bs = ceil(B/S)`` (SPMD
    needs equal shapes); when B is not divisible the trailing slices are
    clamped to ``B - Bs`` and overlap — duplicated docs are computed twice
    and deduplicated at assembly (``doc_slice_owner``), which keeps draws
    bit-identical for *any* batch size.

    Returns (starts (S,) int32, Bs)."""
    if num_docs < 1 or num_shards < 1:
        raise ValueError("num_docs and num_shards must be >= 1")
    per = -(-num_docs // num_shards)   # ceil
    starts = np.minimum(np.arange(num_shards, dtype=np.int64) * per,
                        num_docs - per)
    return starts.astype(np.int32), int(per)


def doc_slice_owner(num_docs: int, num_shards: int):
    """Deduplication map for overlapping slices: for each doc, the shard
    whose slice "officially" covers it plus its row within that slice.

    Returns (owner (B,) int64, row (B,) int64)."""
    starts, per = doc_slice_bounds(num_docs, num_shards)
    d = np.arange(num_docs, dtype=np.int64)
    owner = np.minimum(d // per, num_shards - 1)
    return owner, d - starts[owner]


@dataclasses.dataclass(frozen=True)
class TokenRoutingPlan:
    """Host-side routing plan for one (tokens, mask) batch.

    ``capacity`` is the static per-(requester, owner) bucket size the traced
    routing uses — the measured max bucket load rounded up to a power of two
    (bounded recompiles per shape bucket), clamped to the slice size so it
    can never be exceeded.  The byte counters are *measured* for this batch
    (they depend on the actual token->shard distribution through
    ``capacity``), summed over the whole mesh, counting only off-device
    traffic (the all_to_all diagonal stays local)."""

    num_shards: int
    docs_per_shard: int      # Bs — static doc-slice width
    capacity: int            # per (requester, owner) bucket slots
    routed_tokens: int       # real (unmasked) tokens routed, duplicates incl.
    a2a_bytes: int           # ids + rows all_to_all + per-doc result gather
    psum_bytes: int          # what the dense (B, L, K) psum would have moved


def _next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n - 1).bit_length())


def psum_gather_bytes(batch: int, length: int, num_topics: int,
                      num_shards: int) -> int:
    """Off-device bytes a ring all-reduce of the (B, L, K) int32 gathered
    rows moves across the whole mesh (reduce-scatter + all-gather)."""
    return 4 * 2 * (num_shards - 1) * batch * length * num_topics


def plan_token_routing(word_shard_of: np.ndarray, tokens: np.ndarray,
                       mask: np.ndarray, num_shards: int,
                       num_topics: int) -> TokenRoutingPlan:
    """Measure one batch's routing load and fix the static bucket capacity.

    ``word_shard_of`` is the snapshot's (V,) word->shard map (LPT-balanced
    for trainer-published snapshots, contiguous for re-split dense ones)."""
    tokens = np.asarray(tokens)
    mask = np.asarray(mask, bool)
    B, L = tokens.shape
    S = int(num_shards)
    shard_of = np.asarray(word_shard_of)
    starts, per = doc_slice_bounds(B, S)

    max_bucket, routed = 0, 0
    for s in range(S):
        sl = slice(int(starts[s]), int(starts[s]) + per)
        owners = shard_of[tokens[sl][mask[sl]]]
        routed += owners.size
        if owners.size:
            max_bucket = max(max_bucket,
                             int(np.bincount(owners, minlength=S).max()))
    capacity = min(_next_pow2(max(max_bucket, 1)), per * L)

    K = int(num_topics)
    off = S * (S - 1)   # (src, dst) pairs that actually cross devices
    a2a = 4 * (off * capacity              # token-id request lists
               + off * capacity * K        # gathered rows coming back
               + off * (per * K + 2 * per))  # per-doc theta/sp/ssq gather
    return TokenRoutingPlan(
        num_shards=S, docs_per_shard=per, capacity=capacity,
        routed_tokens=routed, a2a_bytes=a2a,
        psum_bytes=psum_gather_bytes(B, L, K, S))


def route_buckets(owner: Array, payload: Array, num_shards: int,
                  capacity: int):
    """Traced bucketing of a flat token stream by owning shard (the
    shard_map-side half of the routing plan).

    ``owner`` (T,) holds each slot's owning shard, or ``num_shards`` for
    slots that route nowhere (padding).  ``payload`` (T,) is what travels
    (local phi-row ids).  Returns (send (S, C) payload buckets, src (S, C)
    flat source position per slot, T where the slot is empty) — slots the
    plan's capacity guarantees are never dropped for real tokens."""
    T = owner.shape[0]
    order = jnp.argsort(owner)                    # stable in jax.numpy
    sorted_owner = owner[order]
    first = jnp.searchsorted(sorted_owner,
                             jnp.arange(num_shards, dtype=owner.dtype))
    rank = jnp.arange(T, dtype=jnp.int32) - first[
        jnp.clip(sorted_owner, 0, num_shards - 1)].astype(jnp.int32)
    send = jnp.zeros((num_shards, capacity), jnp.int32).at[
        sorted_owner, rank].set(payload[order].astype(jnp.int32),
                                mode="drop")
    src = jnp.full((num_shards, capacity), T, jnp.int32).at[
        sorted_owner, rank].set(order.astype(jnp.int32), mode="drop")
    return send, src


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    """Static description of how the corpus was laid onto the mesh."""

    mode: str                       # "1d" | "2d"
    doc_axes: tuple[str, ...]       # mesh axes carrying document shards
    word_axes: tuple[str, ...]      # mesh axes carrying vocabulary shards
    num_doc_shards: int
    num_word_shards: int
    word_shard_of: np.ndarray | None = None   # (V,) -> word shard (2d)
    word_local_id: np.ndarray | None = None   # (V,) -> local row (2d)
    vocab_shard_size: int = 0                 # padded local V (2d)


def partition_vocabulary(corpus: Corpus, num_shards: int):
    """LPT-balance words over word shards by token count (the paper's C1
    balance rule applied on the vocabulary axis)."""
    counts = np.bincount(corpus.word_ids, minlength=corpus.num_words)
    order = np.argsort(-counts, kind="stable")
    shard_of = np.empty(corpus.num_words, dtype=np.int32)
    local_id = np.empty(corpus.num_words, dtype=np.int32)
    loads = np.zeros(num_shards, dtype=np.int64)
    fill = np.zeros(num_shards, dtype=np.int64)
    for v in order:
        s = int(np.argmin(loads))
        shard_of[v] = s
        local_id[v] = fill[s]
        fill[s] += 1
        loads[s] += int(counts[v])
    return shard_of, local_id, int(fill.max())


def _subset(corpus: Corpus, sel: np.ndarray, word_map: np.ndarray | None,
            num_words_local: int) -> tuple[Corpus, np.ndarray]:
    """Restricted corpus + the canonical indices of the selected tokens."""
    w = corpus.word_ids[sel]
    if word_map is not None:
        w = word_map[w]
    sub = Corpus(corpus.doc_ids[sel].copy(), w.astype(np.int32),
                 corpus.num_docs, num_words_local)
    return sub, np.nonzero(sel)[0].astype(np.int32)


def build_shards(
    corpus: Corpus,
    num_doc_shards: int,
    num_word_shards: int,
    mode: str,
    tile_tokens: int,
) -> tuple[list[TiledCorpusShard], PartitionPlan, list[np.ndarray]]:
    """Host-side shard construction (doc-major, then word order).

    Each shard is tiled once, with numpy fields, padded to the largest
    shard's tile count, which its word counts give (``num_tiles``)."""
    doc_parts = partition_by_document(corpus, num_doc_shards)
    lengths = corpus.doc_lengths()

    if mode == "1d":
        assert num_word_shards == 1
        subs = [(*_subset(corpus, np.isin(corpus.doc_ids, pd), None, corpus.num_words), pd)
                for pd in doc_parts]
        word_meta = (None, None, 0)
    else:
        shard_of, local_id, v_local = partition_vocabulary(corpus, num_word_shards)
        subs = []
        for pd in doc_parts:
            doc_sel = np.isin(corpus.doc_ids, pd)
            for m in range(num_word_shards):
                sel = doc_sel & (shard_of[corpus.word_ids] == m)
                subs.append((*_subset(corpus, sel, local_id, v_local), pd))
        word_meta = (shard_of, local_id, v_local)

    v_total = corpus.num_words
    n_max = max(num_tiles(sub.word_ids, sub.num_words, tile_tokens)
                for sub, _, _ in subs)
    shards = [tile_shard_host(sub, pd, tile_tokens, n_max, token_uid=uid,
                              num_words_total=v_total)
              for sub, uid, pd in subs]
    full_doc_lengths = [lengths[pd] for sub, uid, pd in subs]
    plan = PartitionPlan(mode, (), (), num_doc_shards, num_word_shards,
                         *word_meta)
    return shards, plan, full_doc_lengths


def stack_shards(shards: list[TiledCorpusShard],
                 full_doc_lengths: list[np.ndarray]) -> dict:
    """Stack per-device shards on a leading shard axis -> dict of (G, ...)
    numpy arrays, on the host.

    ``doc_length`` is the *global* per-doc length (in 2D the local bincount
    only sees one word shard's tokens)."""
    d_max = max(s.num_docs_local for s in shards)

    def pad_docs(x, fill=0):
        x = np.asarray(x)
        out = np.full((d_max,), fill, dtype=x.dtype)
        out[: len(x)] = x
        return out

    return dict(
        tile_word=np.stack([s.tile_word for s in shards]),
        token_doc=np.stack([s.token_doc for s in shards]),
        token_mask=np.stack([s.token_mask for s in shards]),
        tile_first=np.stack([s.tile_first for s in shards]),
        doc_length=np.stack([pad_docs(np.asarray(x, np.int32))
                             for x in full_doc_lengths]),
        doc_global=np.stack([pad_docs(s.doc_global, -1) for s in shards]),
        token_uid=np.stack([s.token_uid for s in shards]),
    )


def place_on_mesh(tree, mesh, spec: P):
    """Host arrays committed to ``mesh`` as ``NamedSharding(mesh, spec)``:
    each device receives only its own slice, straight from the host.  On
    a device-free mesh (``AbstractMesh``, for tracing) the host arrays are
    returned as they are."""
    if not isinstance(mesh, Mesh):
        return tree
    return jax.device_put(tree, NamedSharding(mesh, spec))


def partition_counters(shards: list[TiledCorpusShard],
                       sync_bytes: int) -> dict:
    """What a partition costs, counted once from its host shards.

    ``shard_tokens`` and ``shard_tiles`` are each shard's real tokens and
    real tiles, ``tiles_max`` the tile count every shard is padded to,
    ``padding_share`` the padded token slots over all slots, and
    ``sync_bytes`` the bytes each device all-reduces per iteration."""
    tokens = [s.num_tokens for s in shards]
    # real tiles are left-packed: their first slot always holds a token
    tiles = [int(np.asarray(s.token_mask)[:, 0].sum()) for s in shards]
    n_max, t = np.asarray(shards[0].token_mask).shape
    slots = len(shards) * n_max * t
    return dict(shard_tokens=tokens, shard_tiles=tiles, tiles_max=int(n_max),
                padding_share=1.0 - sum(tokens) / max(slots, 1),
                sync_bytes=int(sync_bytes))


def sync_bytes_per_iteration(cfg: core_trainer.LDAConfig, num_words_local: int,
                             heavy_rows: int) -> int:
    """Bytes each device all-reduces per iteration for phi, from shapes and
    dtypes: the int32 (V_local, K) delta, or under ``compressed_sync`` its
    int16 copy plus the int32 heavy rows; once per micro-chunk under
    ``sync_overlap``."""
    K = cfg.num_topics
    if cfg.compressed_sync:
        per = num_words_local * K * 2 + heavy_rows * K * 4
    else:
        per = num_words_local * K * 4
    return per * (cfg.micro_chunks if cfg.sync_overlap else 1)


class DistributedLDA:
    """Mesh-wide LDA: shard_map-wrapped iteration + likelihood.

    1D (paper): ``doc_axes`` = every mesh axis, ``word_axes=()``.
    2D (ours):  ``doc_axes`` = e.g. ("pod","data"), ``word_axes=("model",)``.
    """

    def __init__(self, cfg: core_trainer.LDAConfig, mesh: Mesh, corpus: Corpus,
                 mode: str = "1d",
                 doc_axes: Sequence[str] | None = None,
                 word_axes: Sequence[str] = ("model",)):
        # exactly one resolved config: every closure below binds THIS object
        # (ell_capacity filled), and it is what TrainResult.cfg surfaces
        cfg = core_trainer.resolve_config(cfg, corpus)
        self.cfg = cfg
        self.mesh = mesh
        self.corpus = corpus
        # mesh.shape (not mesh.devices.shape) so an AbstractMesh works too:
        # the collective-contract checker traces the step on device-free
        # meshes to verify axis names and comm accounting.
        axis_sizes = dict(mesh.shape)
        if doc_axes is None:
            doc_axes = tuple(a for a in mesh.axis_names
                             if mode == "1d" or a not in word_axes)
        doc_axes = tuple(doc_axes)
        word_axes = tuple(word_axes) if mode == "2d" else ()
        n_doc = int(np.prod([axis_sizes[a] for a in doc_axes]))
        n_word = int(np.prod([axis_sizes[a] for a in word_axes])) if word_axes else 1

        shards, plan, full_dl = build_shards(corpus, n_doc, n_word, mode,
                                             cfg.tile_tokens)
        self.plan = dataclasses.replace(plan, doc_axes=doc_axes, word_axes=word_axes)
        lead = doc_axes + word_axes     # shard-axis order is doc-major
        dev = P(lead)
        # committed where the step's in_specs put them, so no call moves
        # corpus data and no device holds another's shard
        self.stacked = place_on_mesh(stack_shards(shards, full_dl), mesh, dev)
        # int32-correction rows for the int16 compressed delta sync (empty
        # (G, 0) when off or when no word reaches the flux bound)
        heavy = (heavy_word_rows(corpus, self.plan) if cfg.compressed_sync
                 else np.zeros((n_doc * n_word, 0), np.int32))
        self._heavy = place_on_mesh(heavy, mesh, dev)
        self.num_tokens = corpus.num_tokens
        # static aux only (num_words, num_docs_local): the arrays are on the mesh
        self._template = dataclasses.replace(
            shards[0], **{k: None for k in _CORPUS_FIELDS})
        self.counters = partition_counters(
            shards, sync_bytes_per_iteration(cfg, shards[0].num_words,
                                             heavy.shape[1]))

        repl = P()
        corpus_specs = {k: dev for k in _CORPUS_FIELDS}
        state_specs = core_trainer.LDAState(
            z=dev,
            phi_vk=(repl if mode == "1d" else P(word_axes)),
            phi_sum=repl,
            iteration=repl,
        )
        stats_specs = core_trainer.IterStats(sparse_frac=repl, ell_overflow=repl,
                                             mean_s_over_sq=repl,
                                             row_width_share=repl)

        d_ax = doc_axes if mode == "2d" else lead
        m_ax = word_axes if mode == "2d" else None
        all_ax = lead
        cfg_ = self.cfg
        template = self._template

        def unpack(c: dict) -> TiledCorpusShard:
            return TiledCorpusShard(
                tile_word=c["tile_word"][0], token_doc=c["token_doc"][0],
                token_mask=c["token_mask"][0], tile_first=c["tile_first"][0],
                doc_length=c["doc_length"][0], doc_global=c["doc_global"][0],
                token_uid=c["token_uid"][0],
                num_tokens=template.num_tokens, num_words=template.num_words,
                num_docs_local=c["doc_length"].shape[1],
                num_words_total=template.num_words_total,
            )

        def fold_axes(key):
            for ax in all_ax:
                key = jax.random.fold_in(key, jax.lax.axis_index(ax))
            return key

        def _init(c, key):
            return core_trainer.init_state(cfg_, unpack(c), fold_axes(key),
                                           data_axes=d_ax, model_axes=m_ax)

        def _rebuild(c, z, iteration):
            return core_trainer.state_from_z(cfg_, unpack(c), z, iteration,
                                             data_axes=d_ax, model_axes=m_ax)

        def _step(c, heavy, state, key):
            st, stats = core_trainer.lda_iteration(
                cfg_, unpack(c), state, key, data_axes=d_ax, model_axes=m_ax,
                heavy_rows=heavy[0])
            # the stats' collectives, timed apart from lda.sync
            with jax.named_scope("lda.stats"):
                stats = core_trainer.IterStats(
                    sparse_frac=jax.lax.pmean(stats.sparse_frac, all_ax),
                    ell_overflow=jax.lax.psum(stats.ell_overflow, all_ax)
                    // (n_word if mode == "2d" else 1),
                    mean_s_over_sq=jax.lax.pmean(stats.mean_s_over_sq, all_ax),
                    row_width_share=jax.lax.pmean(stats.row_width_share,
                                                  all_ax),
                )
            return st, stats

        def _ll(c, state):
            # theta term: psum over doc shards only (d_ax is already lead in
            # 1d mode, doc_axes in 2d)
            return core_trainer.log_likelihood(
                cfg_, unpack(c), state, data_axes=d_ax, model_axes=m_ax)

        sm = lambda f, ins, outs: jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=ins, out_specs=outs, check_vma=False))
        self._init_fn = sm(_init, (corpus_specs, repl), state_specs)
        self._rebuild_fn = sm(_rebuild, (corpus_specs, dev, repl), state_specs)
        self._step_fn = sm(_step,
                           (corpus_specs, dev, state_specs, repl),
                           (state_specs, stats_specs))
        self._ll_fn = sm(_ll, (corpus_specs, state_specs), repl)
        self.state_specs = state_specs
        self.corpus_specs = corpus_specs
        self._mode = mode

    # -- public API ---------------------------------------------------------
    def init(self, seed: int | None = None):
        key = jax.random.key(self.cfg.seed if seed is None else seed)
        with self.mesh:
            return self._init_fn(self.stacked, key)

    def step(self, state, key=None):
        if key is None:
            key = jax.random.key(self.cfg.seed + 1)
        with self.mesh:
            return self._step_fn(self.stacked, self._heavy, state, key)

    def log_likelihood(self, state) -> float:
        with self.mesh:
            return float(self._ll_fn(self.stacked, state)) / self.num_tokens

    def restore(self, z_canon: np.ndarray, iteration: int):
        """Elastic restore: canonical z -> state on THIS mesh/partition.

        Works across any device count / partition mode change because counts
        are rebuilt from the re-tiled assignments."""
        from repro.distributed import checkpoint as ckpt
        z_tiled = ckpt.scatter_canonical_z(z_canon, self.stacked["token_uid"])
        z_dev = place_on_mesh(
            z_tiled.reshape(-1, z_tiled.shape[-1]).astype(
                np.dtype(self.cfg.topic_dtype)),
            self.mesh, self.state_specs.z)
        with self.mesh:
            return self._rebuild_fn(self.stacked, z_dev,
                                    jnp.int32(iteration))

    def save_checkpoint(self, mgr, state, extra_meta: dict | None = None):
        from repro.distributed import checkpoint as ckpt
        z_canon = ckpt.gather_canonical_z(state.z, self.stacked["token_uid"],
                                          self.num_tokens)
        meta = dict(extra_meta or {})
        meta.setdefault("mode", self._mode)
        meta.setdefault("fingerprint", ckpt.corpus_fingerprint(self.corpus))
        meta.setdefault("num_topics", self.cfg.num_topics)
        mgr.save(int(jax.device_get(state.iteration)), z_canon, meta)

    # -- serving export -------------------------------------------------------
    def gather_phi(self, state) -> np.ndarray:
        """Canonical (V, K) phi from a state trained on THIS partition.

        1D: phi is replicated — any replica IS the global model.  2D: the
        state's phi_vk is the concatenation of the word shards (the all-gather
        over the word axes that shard_map's out_spec performs), whose rows are
        in (shard, LPT-local row) order — NOT canonical word order.  Exporting
        that array directly would serve a silently permuted model, so we
        un-permute through the partition plan's word maps (and drop the
        padding rows of shards that got fewer than vocab_shard_size words).
        """
        phi = np.asarray(jax.device_get(state.phi_vk))
        if self.plan.mode == "1d":
            return phi
        plan = self.plan
        rows = (plan.word_shard_of.astype(np.int64) * plan.vocab_shard_size
                + plan.word_local_id)
        return phi[rows]

    def _local_word_blocks(self, state) -> list[np.ndarray]:
        """Per-word-shard phi blocks straight off their devices (2D mode).

        ``state.phi_vk`` is word-sharded (replicated over the doc axes); we
        read one addressable shard per word-shard index, so the full (V, K)
        phi is never materialized in one buffer — the point of publishing a
        sharded snapshot from a model too big for one device."""
        v_local = self.plan.vocab_shard_size
        blocks: dict[int, np.ndarray] = {}
        for sh in state.phi_vk.addressable_shards:
            ws = (sh.index[0].start or 0) // v_local
            if ws not in blocks:
                blocks[ws] = np.asarray(sh.data)
        assert len(blocks) == self.plan.num_word_shards
        return [blocks[i] for i in range(self.plan.num_word_shards)]

    def publish_snapshot(self, mgr, state, vocab=None,
                         meta: dict | None = None,
                         shards: int | None = None) -> str:
        """Deprecated: use ``CheckpointManager.publish_snapshot(state,
        partition=self, ...)`` — the one keyword-driven publish entry point
        (same on-disk layout, this just delegates)."""
        warnings.warn(
            "DistributedLDA.publish_snapshot is deprecated; call "
            "CheckpointManager.publish_snapshot(state, partition=dl, ...) "
            "instead", DeprecationWarning, stacklevel=2)
        return mgr.publish_snapshot(state, partition=self, vocab=vocab,
                                    meta=meta, shards=shards)

    def _publish(self, mgr, state, vocab=None, meta: dict | None = None,
                 shards: int | None = None) -> str:
        """Partition-aware snapshot export with the *canonical* phi.

        (The dense single-host path assumes a replicated phi and would write
        a word-sharded, i.e. wrong, snapshot for a 2D-trained state.)

        ``shards``: emit the V-sharded serving layout instead of one dense
        ``.npz``.  When the training partition is 2D and ``shards`` equals
        its word-shard count, each device's local phi block is written
        directly under the trainer's LPT word maps — no full-phi gather
        anywhere.  Any other shard count falls back to gather + contiguous
        re-split."""
        from repro.serve import snapshot as snap_mod

        alpha, beta = self.cfg.resolved_alpha(), self.cfg.beta
        meta_full = dict(meta or {}, mode=self._mode)
        if not shards or shards <= 1:
            state_c = state._replace(
                phi_vk=jnp.asarray(self.gather_phi(state), jnp.int32))
            return mgr._publish_state(
                state_c, alpha, beta,
                num_words_total=self.corpus.num_words, vocab=vocab,
                meta=meta_full)

        plan = self.plan
        if self._mode == "2d" and shards == plan.num_word_shards:
            blocks = self._local_word_blocks(state)
            shard_of, local_id = plan.word_shard_of, plan.word_local_id
            meta_full["layout"] = "lpt"
        else:
            blocks, shard_of, local_id = snap_mod.split_dense_phi(
                self.gather_phi(state), shards)
            meta_full["layout"] = "contiguous"
        return mgr._publish_blocks(
            int(jax.device_get(state.iteration)), blocks,
            np.asarray(jax.device_get(state.phi_sum)), shard_of, local_id,
            alpha=alpha, beta=beta, num_words_total=self.corpus.num_words,
            meta=meta_full, vocab=vocab)

    # -- introspection for tests / roofline ---------------------------------
    def lower_step(self):
        key = jax.random.key(0)
        state = jax.eval_shape(self._init_fn, self.stacked, key)
        return self._step_fn.lower(self.stacked, self._heavy, state, key)

    def compile_step(self):
        """AOT-compile the mesh step; returns ``(step, compile_sec)``.

        The compiled executable is directly callable with concrete inputs,
        so the unified driver (``repro.train.fit``) can report compile time
        separately from sampling throughput — same accounting as the
        single-host path's ``jit(...).lower(...).compile()``."""
        t0 = time.perf_counter()
        compiled = self.lower_step().compile()
        compile_sec = time.perf_counter() - t0

        def step(state, key=None):
            if key is None:
                key = jax.random.key(self.cfg.seed + 1)
            with self.mesh:
                return compiled(self.stacked, self._heavy, state, key)

        return step, compile_sec
