"""Pallas TPU kernel: the CuLDA_CGS sampler (paper §6.1), one word tile per
grid step.

GPU -> TPU mapping:
  * thread block sharing one word's p* in shared memory
        -> the tile's p* row (computed by XLA for every word) DMA'd from HBM
           into VMEM, its C5 search tables built once there and shared by
           every token of the tile;
  * per-token theta/ELL reads from global memory
        -> each real token's (1, P) ELL row DMA'd straight from HBM into a
           per-tile VMEM table (doc ids ride in SMEM), so no per-token
           ``(n, t, P)`` ELL tensor ever exists in HBM; padding slots of a
           tile issue no DMA at all.  An ELL entry's count and topic ride
           packed in one int32 word (``pack_ell``), so a token's row is one
           copy, and the row body splits each word in-register;
  * 32 warp-samplers per block
        -> the tile's t tokens sampled in lock-step on the VPU;
  * 32-ary shared-memory index tree (C5)
        -> 128-lane two-level blocked search (``sampler.search_tables``);
  * short-int compression (C7)
        -> int16 z widened in-register by the wrapper.

Mosaic lowers no ``cumsum`` and no general gather, so the body uses the
shared ``sampler.prefix_sum`` (shifted adds via ``pltpu.roll``) and gathers
by exact selects: p*[topic] is a 128-lane ``take_along_axis`` per 128-topic
block, a per-token pick is a masked lane sum.  Every float op matches
``repro.core.sampler.sample_one_tile`` one for one, so draws are
bit-identical to the ``"sq"`` sweep wherever both run on the same backend
(but for the one draw below).

Row work follows each row block's documents, not the ELL width P.  The
ELL left-packs a document's live topics (``top_k``), so every lane past the
last non-zero count is an exact zero.  Each block of R tokens first takes
the live extent of its real rows (one past their last non-zero count)
rounded up to whole 128-lane vregs, W; one static body per width then runs
the gather, prefix sum and sparse search over W lanes, reading its rows by
strided loads (``table_rows``) so that each vreg holds 8 rows of one
128-lane chunk and the work is W/128 vregs per 8 rows.  Hillis-Steele is
causal, so lanes below W of the W-lane prefix sum equal the P-lane one bit
for bit, and the total S = p1_cum[P-1] is rebuilt under the full sum's
association (``chunk_prefix``).  A draw can differ in one case only: where
u2*S lies within an ulp of S, the P-lane search may count a lane past W (a
zero-count slot past the document's topics), which the W-lane search
cannot.  ``row_width_share`` reports the mean W / P.

Packed ELL words: ``count << b | topic`` with ``b = topic_bits(K)``.  The
count field holds counts up to ``count_limit(K)`` (2,097,151 at K=1024),
which keeps the word non-negative; ``check_count_field`` refuses, on the
host, a corpus whose longest document exceeds it.

Array layout: per-tile rows are ``(n, 1, t)`` and tables ``(rows, 1, width)``
so that every block or DMA takes whole trailing dims (the TPU's (8, 128)
tiling forbids one-row slices of a 2-D array).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sampler import prefix_sum
from repro.kernels.lanes import (VMEM_LIMIT_BYTES, dense_draw, gather_lanes,
                                  lane_pick, row_block, search_rows)

HEAD = 128  # SMEM header lanes before a tile's doc ids: [word, n_real, 0...]
LANES = 128


def topic_bits(K: int) -> int:
    """Low bits of a packed ELL word that hold the topic: enough for K - 1."""
    return max(1, (K - 1).bit_length())


def count_limit(K: int) -> int:
    """The largest count a packed ELL word holds at K topics: the count
    field's ``31 - topic_bits(K)`` bits, so that the word stays >= 0."""
    return (1 << (31 - topic_bits(K))) - 1


def check_count_field(longest_doc: int, K: int) -> None:
    """Refuse a corpus whose longest document (the largest count its ELL can
    hold) does not fit the packed word's count field at K topics."""
    if longest_doc > count_limit(K):
        raise ValueError(
            f"the longest document has {longest_doc} tokens; the sampler "
            f"kernel packs ELL counts into {31 - topic_bits(K)} bits at "
            f"K={K}, which hold at most {count_limit(K)}")


def pack_ell(counts, topics, K: int):
    """One int32 word per ELL entry: ``count << topic_bits(K) | topic``."""
    return (counts << topic_bits(K)) | topics


def unpack_ell(words, K: int):
    """``(counts, topics)`` of packed ELL words, the inverse of ``pack_ell``
    for counts up to ``count_limit(K)`` and topics below K."""
    b = topic_bits(K)
    return words >> b, words & ((1 << b) - 1)


def chunk_prefix(win, P: int):
    """The prefix sum over P lanes of rows that are zero past lane W, from
    ``win`` (R, W): their first W lanes after the prefix-sum steps below
    128 (``prefix_sum(x, stop=128)``), each lane the sum of its 128-lane
    window.  Returns ``(p1_cum, last)``: the sum's first W lanes, and an
    (R, 128) chunk whose last lane is the sum's last lane, the total S, bit
    for bit.

    The later steps (shifts of 128 lanes and more) add whole chunks, so
    they run here as adds of (R, 128) chunks.  The last lane of a chunk
    past W is an exact zero: an add of one is skipped, which leaves the
    same bits, and only the chunks the outputs need are computed.  The
    W-lane sum's own last lane can differ from the P-lane total in the last
    bit whenever W does not divide P; ``last`` cannot."""
    n = P // LANES
    chunks = [win[:, a:a + LANES] for a in range(0, win.shape[1], LANES)]

    @functools.cache
    def chunk(c, d):   # chunk c after the steps of shift below d chunks
        if d == 1:
            return chunks[c] if c < len(chunks) else None
        own, back = chunk(c, d // 2), (
            chunk(c - d // 2, d // 2) if c >= d // 2 else None)
        if own is None or back is None:
            return back if own is None else own
        return own + back

    top = 1 << (n - 1).bit_length()   # the steps run while the shift < n
    cum = [chunk(c, top) for c in range(len(chunks))]
    p1_cum = cum[0] if len(cum) == 1 else jnp.concatenate(cum, axis=1)
    return p1_cum, chunk(n - 1, top)


def _kernel(
    head_ref,       # SMEM (1, HEAD + t) int32: [word, n_real, 0.., doc ids]
    pstar_hbm,      # ANY (V, 1, K) float32 — p*(k) of every word
    ell_hbm,        # ANY (D, 1, P) int32 — packed (count, topic) words
    u1_ref,         # (1, t) float32 — branch uniform
    u2_ref,         # (1, t) float32 — search uniform
    mask_ref,       # (1, t) int32
    z_old_ref,      # (1, t) int32
    z_new_ref,      # out (1, t) int32
    sparse_ref,     # out (1, t) int32 — drew from p1?
    ssq_ref,        # out (1, t) float32 — per-token S/(S+Q), 0 on pads
    width_ref,      # out (1, LANES) int32 — each row block's W, 0 if unsampled
    tok_ell,        # VMEM (t, 1, P) int32 — each token's packed ELL row
    pstar_scr,      # VMEM (1, K) float32 — the tile's p* row
    local_scr,      # VMEM (1, K) float32
    u1_col,         # VMEM (t, 1) float32 — u1 as a column
    u2_col,         # VMEM (t, 1) float32
    z_col,          # VMEM (t, 1) int32 — results as columns
    sp_col,         # VMEM (t, 1) int32
    ssq_col,        # VMEM (t, 1) float32
    sem,            # DMA semaphores (2,): p*, ELL rows
    *,
    alpha: float,
):
    t = z_old_ref.shape[1]
    P = tok_ell.shape[2]
    K = pstar_scr.shape[1]
    R = row_block(t)

    # ---- stage the tile: its p* row and every real token's ELL row ----
    # Three phases carry a ``jax.named_scope``, which Mosaic lowers to a
    # device trace region (one per grid step): ``lda_sample.issue`` (the
    # DMA starts), ``lda_sample.wait`` (the drain) and ``lda_sample.rows``
    # (the row loop).  Regions touch no values, so draws are unchanged.
    n_real = head_ref[0, 1]

    def copy(j, doc):
        return pltpu.make_async_copy(ell_hbm.at[doc], tok_ell.at[j],
                                     sem.at[1])

    def issue(j, carry):
        copy(j, head_ref[0, HEAD + j]).start()
        return carry

    def drain(j, carry):
        copy(0, 0).wait()   # waits count bytes: any same-shape copy
        return carry

    with jax.named_scope("lda_sample.issue"):
        pstar_copy = pltpu.make_async_copy(pstar_hbm.at[head_ref[0, 0]],
                                           pstar_scr, sem.at[0])
        pstar_copy.start()
        jax.lax.fori_loop(0, n_real, issue, 0)
    with jax.named_scope("lda_sample.wait"):
        jax.lax.fori_loop(0, n_real, drain, 0)
        pstar_copy.wait()

    # C7: p* is computed by XLA for every word (``sampler.pstar``, the same
    # division the XLA sweep makes); C5 tables once per tile
    bcum, total, nb = search_rows(pstar_scr[...], local_scr)
    Q = alpha * total                                             # (1, 1)

    # C4 sparse side over each token's ELL row, R tokens at a time, over
    # the block's width (one static body per width).  Rows past n_real hold
    # stale data; everything is row-local and masked out at the end.
    u1_col[...] = jnp.transpose(u1_ref[...])                      # (t, 1)
    u2_col[...] = jnp.transpose(u2_ref[...])
    width_ref[...] = jnp.zeros_like(width_ref)
    block_lane = jax.lax.broadcasted_iota(jnp.int32, width_ref.shape, 1)

    def block(r, r0, W):
        sl = pl.ds(r0, R)
        cnt, tpc = unpack_ell(table_rows(tok_ell, r0, R, W), K)
        cnt = cnt.astype(jnp.float32)
        p1 = cnt * gather_lanes(pstar_scr, tpc)                   # (R, W)
        p1_cum, last = chunk_prefix(
            prefix_sum(p1, roll=pltpu.roll, stop=LANES), P)
        S = lane_pick(last, jnp.full((R, 1), LANES - 1, jnp.int32))  # (R, 1)
        u1, u2 = u1_col[sl], u2_col[sl]
        use_sparse = u1 * (S + Q) < S
        # sparse draw: search the W-entry prefix sums
        jj = jnp.minimum(
            jnp.sum((p1_cum <= u2 * S).astype(jnp.int32), -1, keepdims=True),
            W - 1)
        k_sparse = lane_pick(tpc, jj)
        # dense draw: two-level blocked search (C5)
        k_dense = dense_draw(local_scr, bcum, nb, u2 * total)
        z_col[sl] = jnp.where(use_sparse, k_sparse, k_dense)
        sp_col[sl] = use_sparse.astype(jnp.int32)
        ssq_col[sl] = S / jnp.maximum(S + Q, 1e-30)
        width_ref[...] = jnp.where(block_lane == r, W, width_ref[...])

    def rows(r, carry):
        r0 = pl.multiple_of(r * R, R)
        extent = live_extent(table_rows(tok_ell, r0, R, P), n_real - r0, K)
        for W in range(LANES, P + 1, LANES):
            fits = extent <= W if W == LANES else (
                (extent > W - LANES) & (extent <= W))
            pl.when(fits)(functools.partial(block, r, r0, W))
        return carry

    # only the row blocks holding real tokens (most tiles of the Zipf tail
    # hold a few); the columns of the others are stale and masked out below
    with jax.named_scope("lda_sample.rows"):
        jax.lax.fori_loop(0, (n_real + R - 1) // R, rows, 0)
    mask = mask_ref[...] != 0
    z_new_ref[...] = jnp.where(mask, jnp.transpose(z_col[...]),
                               z_old_ref[...])
    sparse_ref[...] = jnp.where(mask, jnp.transpose(sp_col[...]), 0)
    ssq_ref[...] = jnp.where(mask, jnp.transpose(ssq_col[...]), 0.0)


def grid_layout(n: int, t: int, K: int, P: int):
    """Launch geometry: ``(grid, in_specs, out_specs, scratch_shapes)``.

    Single source of truth — ``lda_sample_tiles`` launches from this and the
    ``kernel-contract`` checker (``contract.py``) enumerates it."""
    row = pl.BlockSpec((None, 1, t), lambda i: (i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((None, 1, HEAD + t), lambda i: (i, 0, 0),
                     memory_space=pltpu.SMEM),
        hbm,                                              # p* rows
        hbm,                                              # packed ELL rows
        row, row, row, row,                               # u1, u2, mask, z
    ]
    out_specs = [row, row, row,
                 pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0))]
    scratch_shapes = [
        pltpu.VMEM((t, 1, P), jnp.int32),
        pltpu.VMEM((1, K), jnp.float32),
        pltpu.VMEM((1, K), jnp.float32),
        pltpu.VMEM((t, 1), jnp.float32),
        pltpu.VMEM((t, 1), jnp.float32),
        pltpu.VMEM((t, 1), jnp.int32),
        pltpu.VMEM((t, 1), jnp.int32),
        pltpu.VMEM((t, 1), jnp.float32),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    return (n,), in_specs, out_specs, scratch_shapes


def table_rows(ref, r0, R: int, W: int):
    """Rows ``r0 .. r0+R`` of a (t, 1, P) VMEM table, their first W lanes,
    as an (R, W) array.  Each 128-lane chunk is one strided load from the
    table's (t * P/128, 128) view, so a vreg holds 8 rows of one chunk; an
    (R, 1, W) load holds one row a vreg, whatever W, and the row work
    would then cost R vregs an op at every W up to 1024."""
    t, _, P = ref.shape
    n = P // LANES
    flat = ref.reshape(t * n, LANES)
    parts = [flat[pl.ds(r0 * n + c, R, stride=n), :]
             for c in range(W // LANES)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def live_extent(words, n_rows, K: int):
    """One past the last non-zero count of the first ``n_rows`` rows of the
    (R, P) packed ELL words ``words``: the lanes those rows' documents use
    (their live-topic count, for a ``top_k`` ELL).  Later rows are
    ignored."""
    row = jax.lax.broadcasted_iota(jnp.int32, words.shape, 0)
    # a padding lane's word may hold a topic but has count 0; words are
    # >= 0 and order by count, so a lane is live in some row iff the count
    # of its row max is > 0
    most = jnp.max(jnp.where(row < n_rows, words, 0), axis=0, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, most.shape, 1)
    return jnp.max(jnp.where(most >> topic_bits(K) > 0, lane + 1, 0))


def row_width_share(widths, ell_width: int):
    """Mean over the sampled row blocks (``widths`` > 0, as
    ``lda_sample_tiles`` returns them) of W over the padded ELL width."""
    P = -(-ell_width // LANES) * LANES
    sampled = widths > 0
    return jnp.sum(widths) / P / jnp.maximum(sampled.sum(), 1)


def tile_head(tile_word, token_doc, token_mask):
    """The per-tile SMEM header ``(n, 1, HEAD + t)``: word id, real-token
    count, then the doc id of every slot (real tokens are left-packed)."""
    n, t = token_doc.shape
    meta = jnp.zeros((n, HEAD), jnp.int32)
    meta = meta.at[:, 0].set(tile_word).at[:, 1].set(token_mask.sum(1))
    return jnp.concatenate([meta, token_doc], axis=1).reshape(n, 1, HEAD + t)


def lda_sample_tiles(
    tile_word,     # (n,) int32
    token_doc,     # (n, t) int32
    pstar_vk,      # (V, K) float32 — ``sampler.pstar`` of every word
    ell_counts,    # (D, P) int32 — per-DOC ELL, fetched per real token
    ell_topics,    # (D, P) int32
    u1,            # (n, t) float32
    u2,            # (n, t) float32
    token_mask,    # (n, t) int32
    z_old,         # (n, t) int32
    *,
    alpha: float,
    interpret: bool,
):
    """pallas_call wrapper: grid over word tiles.  Returns (z_new, sparse,
    ssq), all (n, t), and each row block's width ``(n, t // R)``: the ELL
    lanes it sampled, 0 for a block past the tile's real tokens.

    The counts and topics are packed into one word an entry
    (``pack_ell``), in the fusion that pads the ELL width to whole 128-lane
    vregs: the extra entries have count 0, add exact zeros to every prefix
    sum and are never drawn.  Counts must not exceed ``count_limit(K)``
    (``check_count_field`` checks the corpus on the host)."""
    n, t = z_old.shape
    V, K = pstar_vk.shape
    ell = jnp.pad(pack_ell(ell_counts, ell_topics, K),
                  ((0, 0), (0, -ell_counts.shape[1] % LANES)))
    D, P = ell.shape
    nb = t // row_block(t)
    if nb > LANES:
        raise ValueError(f"{nb} row blocks a tile; at most {LANES}")
    grid, in_specs, out_specs, scratch_shapes = grid_layout(n, t, K, P)
    kern = functools.partial(_kernel, alpha=alpha)
    rows = lambda a: a.reshape(n, 1, t)  # noqa: E731
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
        out_shape=[
            jax.ShapeDtypeStruct((n, 1, t), jnp.int32),
            jax.ShapeDtypeStruct((n, 1, t), jnp.int32),
            jax.ShapeDtypeStruct((n, 1, t), jnp.float32),
            jax.ShapeDtypeStruct((n, 1, LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="lda_sample",
    )(tile_head(tile_word, token_doc, token_mask),
      pstar_vk.reshape(V, 1, K), ell.reshape(D, 1, P),
      rows(u1), rows(u2), rows(token_mask), rows(z_old))
    z_new, sparse, ssq, widths = out
    return (z_new.reshape(n, t), sparse.reshape(n, t), ssq.reshape(n, t),
            widths.reshape(n, LANES)[:, :nb])
