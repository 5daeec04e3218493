"""kernel-contract metadata for the fused training-sweep kernel.

The cases re-derive the launch geometry from ``kernel.grid_layout`` (the
same call ``lda_sample_tiles`` launches from) and run the kernel's own
``tile_head`` over real tilings, so the checker sees the SMEM header the
kernel's DMA loop reads: word id, real-token count and doc ids.  Each case
also runs its first tiles through the kernel (interpret mode) over an ELL
of heavy-tailed live-topic counts and checks the width each row block
sampled.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.analysis.contracts import ContractCase, KernelContract, Operand
from repro.kernels.lanes import row_block
from repro.kernels.lda_sample import kernel

# Declared VMEM blocks + scratch (the one (t, 1, P) table of per-token packed
# ELL rows dominates: 4 MiB at t=256, P=512); the kernel raises Mosaic's
# scoped limit to ``kernel.VMEM_LIMIT_BYTES`` for its body's temporaries.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
WIDTH_TILES = 2  # tiles of a case run through the kernel for its widths


def _case(name: str, *, n: int, t: int, V: int, K: int, D: int, P: int,
          fill: float = 0.6) -> ContractCase:
    token_doc = ((2 * (np.arange(n)[:, None]) + np.arange(t)[None, :] % 4)
                 % D).astype(np.int32)
    tile_word = (np.arange(n, dtype=np.int32) * 7) % V
    n_real = np.maximum(1, (fill * t * (1 + np.arange(n) % 3) / 3)
                        ).astype(int)
    token_mask = np.arange(t)[None, :] < n_real[:, None]
    return _build(name, token_doc, tile_word, token_mask, V=V, K=K, D=D, P=P)


def _shard_case(name: str, *, K: int, P: int,
                shard_index: int = 1) -> ContractCase:
    """Shard-local geometry: one shard of a real 2d (doc x word) partition —
    ``tile_word`` holds LPT-local row ids into a padded per-shard
    vocabulary, ``token_doc`` shard-local doc ids over an irregular doc
    subset, padding tiles included."""
    from repro.core.corpus import Corpus
    from repro.distributed import partition

    rng = np.random.default_rng(5)
    D_glob, V_glob, per_doc, t = 10, 30, 24, 8
    corpus = Corpus(np.repeat(np.arange(D_glob, dtype=np.int32), per_doc),
                    rng.integers(0, V_glob, D_glob * per_doc,
                                 dtype=np.int32).astype(np.int32),
                    D_glob, V_glob)
    shards, _, _ = partition.build_shards(corpus, 2, 2, "2d", t)
    s = shards[shard_index]
    return _build(name, np.asarray(s.token_doc), np.asarray(s.tile_word),
                  np.asarray(s.token_mask), V=s.num_words, K=K,
                  D=s.num_docs_local, P=P)


def _build(name: str, token_doc: np.ndarray, tile_word: np.ndarray,
           token_mask: np.ndarray, *, V: int, K: int, D: int,
           P: int) -> ContractCase:
    n, t = token_doc.shape
    grid, in_specs, out_specs, scratch = kernel.grid_layout(n, t, K, P)
    head = np.asarray(kernel.tile_head(tile_word, token_doc, token_mask))

    def header_round_trip():
        # the DMA loop reads word, n_real and the first n_real doc ids from
        # SMEM: they must re-derive the tiling, and real tokens must be
        # left-packed (slots past n_real are never fetched)
        msgs = []
        n_real = token_mask.sum(1)
        if not np.array_equal(head[:, 0, 0], tile_word):
            msgs.append("header word ids do not round-trip to tile_word")
        if not np.array_equal(head[:, 0, 1], n_real):
            msgs.append("header token counts != real tokens per tile")
        if not np.array_equal(head[:, 0, kernel.HEAD:], token_doc):
            msgs.append("header doc ids do not round-trip to token_doc")
        packed = np.arange(t)[None, :] < n_real[:, None]
        if not np.array_equal(packed, token_mask.astype(bool)):
            bad = int(np.argwhere((packed != token_mask).any(1))[0][0])
            msgs.append(f"tile {bad}: real tokens are not left-packed")
        if not ((token_doc[token_mask.astype(bool)] < D).all()
                and (tile_word < V).all()):
            msgs.append("a fetched doc or word row lies outside its table")
        return msgs

    def block_widths():
        # each row block holding a real token samples the smallest whole-
        # vreg width that covers its real tokens' documents' live topics
        # (and no more than the padded P); the others sample nothing
        m = min(n, WIDTH_TILES)
        Pp = -(-P // kernel.LANES) * kernel.LANES
        live = np.minimum(P, 1 + (np.arange(D) * 97) % (2 * P))
        counts = (np.arange(P) < live[:, None]).astype(np.int32)
        topics = np.broadcast_to(np.arange(P, dtype=np.int32) % K, (D, P))
        rng = np.random.default_rng(0)
        u = rng.random((2, m, t), dtype=np.float32)
        *_, widths = kernel.lda_sample_tiles(
            jnp.asarray(tile_word[:m]), jnp.asarray(token_doc[:m]),
            jnp.asarray(rng.random((V, K), dtype=np.float32)),
            jnp.asarray(counts), jnp.asarray(topics), jnp.asarray(u[0]),
            jnp.asarray(u[1]), jnp.asarray(token_mask[:m], np.int32),
            jnp.zeros((m, t), jnp.int32), alpha=0.1, interpret=True)
        R = row_block(t)
        real = token_mask[:m].astype(bool)
        need = np.where(real, live[token_doc[:m]], 0).reshape(m, -1, R)
        want = np.where(real.reshape(m, -1, R).any(2),
                        np.maximum(-(-need.max(2) // kernel.LANES), 1)
                        * kernel.LANES, 0)
        widths = np.asarray(widths)
        msgs = []
        if (widths % kernel.LANES).any() or (widths > Pp).any():
            msgs.append("a row block's width is not whole vregs within P")
        if not np.array_equal(widths, want):
            msgs.append("a row block's width is not the smallest one "
                        "covering its real tokens' live topics")
        return msgs

    row = (n, 1, t)
    in_shapes = [
        Operand("head", (n, 1, kernel.HEAD + t), jnp.int32, in_specs[0]),
        Operand("pstar", (V, 1, K), jnp.float32, in_specs[1]),
        Operand("ell", (D, 1, P), jnp.int32, in_specs[2]),
        Operand("u1", row, jnp.float32, in_specs[3]),
        Operand("u2", row, jnp.float32, in_specs[4]),
        Operand("mask", row, jnp.int32, in_specs[5]),
        Operand("z_old", row, jnp.int32, in_specs[6]),
    ]
    out_shapes = [
        Operand("z_new", row, jnp.int32, out_specs[0]),
        Operand("sparse", row, jnp.int32, out_specs[1]),
        Operand("ssq", row, jnp.float32, out_specs[2]),
        Operand("width", (n, 1, kernel.LANES), jnp.int32, out_specs[3]),
    ]
    return ContractCase(
        name=name, grid=grid,
        inputs=tuple(in_shapes), outputs=tuple(out_shapes),
        scratch=tuple(scratch),
        coverage=("z_new", "sparse", "ssq", "width"),
        extra_checks=(header_round_trip, block_widths))


def contract() -> KernelContract:
    return KernelContract(
        kernel="lda_sample",
        vmem_budget_bytes=VMEM_BUDGET_BYTES,
        cases=(
            _case("tiny", n=8, t=16, V=12, K=32, D=6, P=4),
            # NYTimes-like: K=1024, 256-token tiles, ELL width 512
            _case("paper", n=128, t=256, V=512, K=1024, D=2048, P=512),
            # the PubMed cell's ELL width, 809 padded to 896 (7 row bodies),
            # in its 256-token tiles: the packed ELL table counts 7 MiB
            _case("pubmed", n=128, t=256, V=512, K=1024, D=2048, P=896),
            # one real 2d-partition shard: local vocab rows, irregular doc
            # subset, padding tiles
            _shard_case("shard2d", K=48, P=6),
        ))
