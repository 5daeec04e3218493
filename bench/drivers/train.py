"""Training cells: iterations of the program's compiled LDA step.

Set-up generates the configuration's planted corpus from the seed, tiles
it with the program's ``tile_shard`` (what ``repro.train.fit`` does for one
device, without its second, padding pass), builds the state from the
planted topics with ``trainer.state_from_z`` and compiles
``trainer.lda_iteration`` as ``repro.train`` compiles it (the shard an
argument, not a constant).  One iteration warms it up.  The window then
runs whole iterations back to back, each ending in ``block_until_ready``,
until ``seconds`` have passed, and the same state carries on throughout.

The check compares the window's last iteration with ``bench.reference``:
the draws of every token of a sample of documents drawn from the seed
(the longest document always among them), and the topic-word counts of
the final state against a recount of its assignments.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np

from bench import generator, reference
from bench.work import lda_sample as work

CHECK_DOCS = 128        # documents whose tokens' draws are compared
TOKEN_BLOCK = 4096      # tokens per block of the reference's draws


@dataclasses.dataclass
class Corpus:
    """The benchmark's own copy of the corpus (document-major)."""

    doc_ids: np.ndarray
    word_ids: np.ndarray
    num_docs: int
    num_words: int


def key_for(seed: int):
    """A threefry key from all the bits of ``seed``."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def lda_config(cfg: dict):
    from repro.core.trainer import LDAConfig

    return LDAConfig(num_topics=int(cfg["num_topics"]),
                     alpha=float(cfg["alpha"]), beta=float(cfg["beta"]),
                     tile_tokens=int(cfg["tile_tokens"]),
                     **cfg["train"])


def setup(ctx):
    import jax
    import jax.numpy as jnp

    from repro.core import corpus as pcorpus
    from repro.core import trainer

    cfg = ctx.config
    D, V = int(cfg["num_docs"]), int(cfg["num_words"])
    with ctx.span("bench.generate"):
        d, w, z = generator.training_corpus(cfg, ctx.seed)
    corpus = Corpus(d, w, D, V)
    program_corpus = pcorpus.Corpus(d, w, D, V)
    lcfg = trainer.resolve_config(lda_config(cfg), program_corpus)
    with ctx.span("bench.tile"):
        shard = pcorpus.tile_shard(program_corpus, np.arange(D, dtype=np.int32),
                                   lcfg.tile_tokens)
    with ctx.span("bench.state"):
        layout = {k: np.asarray(getattr(shard, k))
                  for k in ("tile_word", "token_doc", "token_mask",
                            "token_uid")}
        uid = layout["token_uid"]
        z_tiled = np.where(uid >= 0, z[np.maximum(uid, 0)], 0)
        state = jax.jit(functools.partial(trainer.state_from_z, lcfg))(
            shard, jnp.asarray(z_tiled.astype(lcfg.topic_dtype)), 0)
    key = key_for(ctx.seed)
    with ctx.span("bench.compile"):
        compiled = jax.jit(functools.partial(trainer.lda_iteration, lcfg)
                           ).lower(shard, state, key).compile()
    with ctx.span("bench.warmup"):
        state, _ = compiled(shard, state, key)
        state.z.block_until_ready()
    ctx.log(f"corpus: {D} docs, V={V}, {shard.num_tokens} tokens in "
            f"{layout['tile_word'].shape[0]} tiles; ELL width "
            f"{lcfg.ell_capacity}; sampler {lcfg.sampler}")
    return dict(cfg=cfg, lcfg=lcfg, corpus=corpus, shard=shard,
                layout=layout, key=key, step=compiled, state=state,
                prev=None, iterations=1, num_tokens=shard.num_tokens,
                hlo=[compiled.as_text()] if ctx.trace else [])


def hlo_texts(st) -> list[str]:
    """The compiled programs the window ran, for the trace reduction."""
    return st["hlo"]


def window(st, ctx, seconds: float) -> dict:
    """Whole iterations for ``seconds``; the rate is every real token of
    every iteration over the time from the window's start to the end of
    its last iteration."""
    step, shard, key = st["step"], st["shard"], st["key"]
    state, n = st["state"], 0
    t0 = time.perf_counter()
    while True:
        with ctx.span("train.iteration"):
            prev = state
            state, _ = step(shard, state, key)
            state.z.block_until_ready()
        n += 1
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            break
    st.update(state=state, prev=prev, iterations=st["iterations"] + n,
              window_iterations=n)
    tokens = n * st["num_tokens"]
    return dict(metrics={"train_tokens_per_s": tokens / (t1 - t0)},
                attempted=tokens, failed=0, window_s=t1 - t0,
                iterations=n, tokens=tokens)


def release(st) -> None:
    """Drop the program's compiled step and every device array but the
    final counts; bring the assignments the check reads to the host."""
    prev, state = st.pop("prev"), st.pop("state")
    st["z_prev"] = np.asarray(prev.z)
    st["z_end"] = np.asarray(state.z)
    st["phi_end"], st["phi_sum_end"] = state.phi_vk, state.phi_sum
    for k in ("step", "shard"):
        st.pop(k)
    del prev, state


def _device_checks():
    """Jitted integer checks on the device (exact; built on first use)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def layout_errors(w, d, uid, mask, tile_word, token_doc):
        T = w.shape[0]
        ok = mask & (uid >= 0) & (uid < T)
        u = jnp.where(ok, uid, 0)
        bad = jnp.sum(mask & ~ok)
        bad += jnp.sum(ok & (w[u] != tile_word[:, None]))
        bad += jnp.sum(ok & (d[u] != token_doc))
        held = jnp.zeros(T, jnp.int32).at[u.ravel()].add(
            ok.ravel().astype(jnp.int32))
        return bad + jnp.sum(jnp.abs(held - 1))

    @functools.partial(jax.jit, static_argnums=(3, 4))
    def recount(z, tile_word, mask, V, K):
        z = z.astype(jnp.int32)
        flat = jnp.broadcast_to(tile_word[:, None], z.shape) * K + z
        ok = mask & (z >= 0) & (z < K)
        counts = jnp.zeros(V * K, jnp.int32).at[
            jnp.where(ok, flat, 0).ravel()].add(ok.ravel().astype(jnp.int32))
        return counts.reshape(V, K), jnp.sum(mask & ~ok)

    @jax.jit
    def count_errors(counts, phi, phi_sum):
        return (jnp.sum(counts != phi)
                + jnp.sum(counts.sum(axis=0) != phi_sum))

    return layout_errors, recount, count_errors


def slots_of(layout: dict, T: int) -> np.ndarray:
    """The tiled slot of every corpus token."""
    real = np.flatnonzero(layout["token_mask"].ravel())
    out = np.full(T, -1, np.int64)
    out[layout["token_uid"].ravel()[real]] = real
    return out


def check_docs(corpus: Corpus, seed: int) -> np.ndarray:
    """A sample of documents drawn from the seed, the longest among them."""
    lens = np.bincount(corpus.doc_ids, minlength=corpus.num_docs)
    pick = generator.rng_for(seed, 9).choice(
        corpus.num_docs, min(CHECK_DOCS, corpus.num_docs), replace=False)
    return np.unique(np.concatenate([pick, [int(np.argmax(lens))]]))


def reference_draws(st, seed: int, dtype) -> tuple[np.ndarray, np.ndarray]:
    """The reference's draws of the window's last iteration for the tokens
    of the check documents, and the program's draws of the same tokens."""
    import jax
    import jax.numpy as jnp

    corpus, layout, cfg = st["corpus"], st["layout"], st["cfg"]
    K, V = int(cfg["num_topics"]), corpus.num_words
    T = len(corpus.doc_ids)
    _, recount, _ = st.setdefault("device_checks", _device_checks())
    phi, _ = recount(jnp.asarray(st["z_prev"]),
                     jnp.asarray(layout["tile_word"]),
                     jnp.asarray(layout["token_mask"]), V, K)
    phi_sum = np.asarray(phi.sum(axis=0)).astype(np.int64)
    docs = check_docs(corpus, seed)
    starts = np.searchsorted(corpus.doc_ids, docs)
    ends = np.searchsorted(corpus.doc_ids, docs, "right")
    toks = np.concatenate([np.arange(a, b) for a, b in zip(starts, ends)])
    local = np.repeat(np.arange(len(docs)), ends - starts)
    slot = st.setdefault("slots", slots_of(layout, T))[toks]
    n_tiles, t = layout["token_mask"].shape
    tile, lane = np.divmod(slot, t)
    z_prev = st["z_prev"].ravel()[slot].astype(np.int64)
    got = st["z_end"].ravel()[slot].astype(np.int64)
    theta = reference.topic_counts(local, z_prev, len(docs), K)
    it_key = jax.random.fold_in(st["key"], st["iterations"] - 1)
    keys = jax.random.split(it_key, n_tiles)
    need, inv = np.unique(tile, return_inverse=True)
    u = np.asarray(jax.vmap(lambda k: jax.random.uniform(k, (t, 2)))(
        keys[need]))[inv, lane]
    words = corpus.word_ids[toks]
    rows = np.asarray(phi[jnp.asarray(words)]).astype(np.int64)
    want = np.empty(len(toks), np.int32)
    counts, topics = reference.ell_order(theta)
    for a in range(0, len(toks), TOKEN_BLOCK):
        b = slice(a, a + TOKEN_BLOCK)
        ps = reference.pstar(rows[b], phi_sum, float(cfg["beta"]), V, dtype)
        want[b] = reference.draw(ps, counts[local[b]], topics[local[b]],
                                 u[b, 0], u[b, 1], float(cfg["alpha"]),
                                 dtype)
    return want, got


def check(st, ctx) -> list[tuple[str, float]]:
    """The numbers compared, by name; ``ctx.limits`` holds their limits."""
    import jax.numpy as jnp

    corpus, layout, cfg = st["corpus"], st["layout"], st["cfg"]
    K, V = int(cfg["num_topics"]), corpus.num_words
    layout_errors, recount, count_errors = st.setdefault(
        "device_checks", _device_checks())
    tw, mask = (jnp.asarray(layout["tile_word"]),
                jnp.asarray(layout["token_mask"]))
    bad_layout = int(layout_errors(
        jnp.asarray(corpus.word_ids), jnp.asarray(corpus.doc_ids),
        jnp.asarray(layout["token_uid"]), mask, tw,
        jnp.asarray(layout["token_doc"])))
    if bad_layout:
        return [("layout_errors", bad_layout)]
    counts, invalid = recount(jnp.asarray(st["z_end"]), tw, mask, V, K)
    bad_counts = int(invalid) + int(count_errors(
        counts, st["phi_end"], st["phi_sum_end"]))
    del counts
    want, got = reference_draws(st, ctx.seed, np.float64)
    return [("layout_errors", bad_layout),
            ("count_errors", bad_counts),
            ("draw_mismatch_share", float((want != got).mean()))]


def control(st, ctx) -> list[tuple[str, float]]:
    """The control: the reference in bfloat16 in the program's place."""
    import ml_dtypes

    want, _ = reference_draws(st, ctx.seed, np.float64)
    low, _ = reference_draws(st, ctx.seed, ml_dtypes.bfloat16)
    return [("draw_mismatch_share", float((want != low).mean()))]


def work_counts(st) -> dict:
    """The Table 1 counts of one iteration, from the final state."""
    corpus, cfg = st["corpus"], st["cfg"]
    K = int(cfg["num_topics"])
    T = len(corpus.doc_ids)
    slot = st.setdefault("slots", slots_of(st["layout"], T))
    z = st["z_end"].ravel()[slot].astype(np.int64)
    live = np.unique(corpus.doc_ids.astype(np.int64) * K + z)
    kd = np.bincount(live // K, minlength=corpus.num_docs)
    lens = np.bincount(corpus.doc_ids, minlength=corpus.num_docs)
    kd_sum = int((kd * lens).sum())
    distinct = int(np.unique(corpus.word_ids).size)
    return dict(sampler=work.sampler(kd_sum, distinct, K),
                plan=work.plan(T, int(kd.sum())),
                count_update=work.count_update(T),
                mean_kd_per_token=kd_sum / T)
