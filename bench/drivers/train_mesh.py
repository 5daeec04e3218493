"""Training cells on a mesh: iterations of the program's ``DistributedLDA``
step, which is what ``repro.train.fit(mesh=...)`` runs.

Set-up generates the configuration's planted corpus from the seed and
builds ``DistributedLDA`` over a mesh of the cell's chips with the
configuration's axes and partition mode: documents split over the chips,
balanced on tokens, each chip's shard tiled on the host.  The state starts
from the planted topics (``restore``), the step is compiled ahead of time
(``compile_step``) and one iteration warms it up.  The window then runs
whole iterations back to back, each ending in ``block_until_ready``, until
``seconds`` have passed; the rate counts every real token of the corpus in
every iteration.

The check compares the window's output with ``bench.reference``:

* ``layout_errors``: every corpus token is held by exactly one slot of one
  shard, a slot of its word and its document;
* ``count_errors``: the final assignments, put back in corpus order
  through ``token_uid`` and recounted, against phi and its column sums as
  the program returns them;
* ``replica_errors``: every chip's own phi and phi_sum replica against the
  same recount (the sync has to keep the replicas equal);
* ``draw_mismatch_share``: the reference's draws of the window's last
  iteration for every token of a sample of documents (the longest among
  them), on the uniforms each token's shard drew: the iteration's key
  folded with the shard's index, then split over the shard's tiles.
"""
from __future__ import annotations

import functools
import json
import time

import numpy as np

from bench import generator, reference
from bench.drivers import train as single
from bench.work import lda_sync


def setup(ctx):
    import jax
    from jax.sharding import Mesh

    from repro.core import corpus as pcorpus
    from repro.distributed.partition import DistributedLDA

    cfg = ctx.config
    dep = cfg["deployment"]
    D, V = int(cfg["num_docs"]), int(cfg["num_words"])
    with ctx.span("bench.generate"):
        d, w, z = generator.training_corpus(cfg, ctx.seed)
    devs = jax.devices()[:ctx.chips]
    mesh = Mesh(np.array(devs), tuple(dep["mesh_axes"]))
    with ctx.span("bench.partition"):
        dl = DistributedLDA(single.lda_config(cfg), mesh,
                            pcorpus.Corpus(d, w, D, V), mode=dep["mode"])
    with ctx.span("bench.state"):
        state = dl.restore(z, 0)
    key = single.key_for(ctx.seed)
    with ctx.span("bench.compile"):
        step, _ = dl.compile_step()
        hlo = [dl.lower_step().compile().as_text()] if ctx.trace else []
    with ctx.span("bench.warmup"):
        state, _ = step(state, key)
        state.z.block_until_ready()
    G, n, t = dl.stacked["token_mask"].shape
    ctx.log(f"corpus: {D} docs, V={V}, {len(d)} tokens on {G} chips "
            f"({dep['mode']}), {n} tiles a chip; ELL width "
            f"{dl.cfg.ell_capacity}; sampler {dl.cfg.sampler}")
    counters = getattr(dl, "counters", None)
    if counters is not None:
        ctx.log(f"partition: {json.dumps(counters)}")
    return dict(cfg=cfg, corpus=single.Corpus(d, w, D, V), dl=dl, key=key,
                step=step, state=state, prev=None, iterations=1,
                num_tokens=len(d), devs=devs, chips=len(devs),
                kind=devs[0].device_kind, hlo=hlo)


def hlo_texts(st) -> list[str]:
    """The compiled programs the window ran, for the trace reduction."""
    return st["hlo"]


def window(st, ctx, seconds: float) -> dict:
    """Whole iterations for ``seconds``; the rate is every real token of
    every iteration over the time from the window's start to the end of
    its last iteration."""
    step, key = st["step"], st["key"]
    state, n = st["state"], 0
    t0 = time.perf_counter()
    while True:
        with ctx.span("train.iteration"):
            prev = state
            state, _ = step(state, key)
            state.z.block_until_ready()
        n += 1
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            break
    st.update(state=state, prev=prev, iterations=st["iterations"] + n,
              window_iterations=n)
    peaks = [(dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for dv in st["devs"]]
    ctx.log(f"memory peak per chip: {peaks}")
    tokens = n * st["num_tokens"]
    return dict(metrics={"train_tokens_per_s": tokens / (t1 - t0)},
                attempted=tokens, failed=0, window_s=t1 - t0,
                iterations=n, tokens=tokens)


def release(st) -> None:
    """Drop the compiled step and the corpus on the chips; bring the
    layout and the assignments the check reads to the host, and keep the
    final phi and phi_sum replicas on their chips."""
    prev, state, dl = st.pop("prev"), st.pop("state"), st.pop("dl")
    c = {k: np.asarray(dl.stacked[k]) for k in
         ("tile_word", "token_doc", "token_mask", "token_uid", "doc_global")}
    G, n, t = c["token_mask"].shape
    docs = np.take_along_axis(c["doc_global"], c["token_doc"].reshape(G, -1),
                              axis=1)
    st["layout"] = {"tile_word": c["tile_word"].reshape(G * n),
                    "token_doc": docs.reshape(G * n, t),
                    "token_mask": c["token_mask"].reshape(G * n, t),
                    "token_uid": c["token_uid"].reshape(G * n, t)}
    st["shard_tiles"], st["tile_tokens"] = n, t
    st["z_prev"] = np.asarray(prev.z)
    st["z_end"] = np.asarray(state.z)
    st["phi_end"], st["phi_sum_end"] = state.phi_vk, state.phi_sum
    st.pop("step")
    del prev, state, dl


@functools.cache
def _recount():
    """Jitted: (V, K) counts of the tokens' words and topics, in corpus
    order, and the number of topics outside [0, K) (exact; on the device)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def recount(w, z, V: int, K: int):
        z = z.astype(jnp.int32)
        ok = (z >= 0) & (z < K)
        counts = jnp.zeros(V * K, jnp.int32).at[
            jnp.where(ok, w * K + z, 0)].add(ok.astype(jnp.int32))
        return counts.reshape(V, K), jnp.sum(~ok)

    return recount


def canonical(st, z_tiled: np.ndarray) -> np.ndarray:
    """Tiled assignments put back in corpus order through ``token_uid``."""
    T = len(st["corpus"].doc_ids)
    slots = st.setdefault("slots", single.slots_of(st["layout"], T))
    return z_tiled.ravel()[slots]


def reference_draws(st, seed: int, dtype) -> tuple[np.ndarray, np.ndarray]:
    """The reference's draws of the window's last iteration for the tokens
    of the check documents, and the program's draws of the same tokens."""
    import jax
    import jax.numpy as jnp

    corpus, cfg = st["corpus"], st["cfg"]
    K, V = int(cfg["num_topics"]), corpus.num_words
    z_prev = canonical(st, st["z_prev"])
    phi, _ = _recount()(jnp.asarray(corpus.word_ids), jnp.asarray(z_prev),
                        V, K)
    phi_sum = np.asarray(phi.sum(axis=0)).astype(np.int64)
    docs = single.check_docs(corpus, seed)
    starts = np.searchsorted(corpus.doc_ids, docs)
    ends = np.searchsorted(corpus.doc_ids, docs, "right")
    toks = np.concatenate([np.arange(a, b) for a, b in zip(starts, ends)])
    local = np.repeat(np.arange(len(docs)), ends - starts)
    n, t = st["shard_tiles"], st["tile_tokens"]
    tile, lane = np.divmod(st["slots"][toks], t)
    shard, tile = np.divmod(tile, n)
    got = canonical(st, st["z_end"])[toks].astype(np.int64)
    theta = reference.topic_counts(local, z_prev[toks], len(docs), K)
    it_key = jax.random.fold_in(st["key"], st["iterations"] - 1)
    u = np.empty((len(toks), 2), np.float32)
    for g in np.unique(shard):
        on = shard == g
        keys = jax.random.split(jax.random.fold_in(it_key, int(g)), n)
        need, inv = np.unique(tile[on], return_inverse=True)
        u[on] = np.asarray(jax.vmap(lambda k: jax.random.uniform(k, (t, 2)))(
            keys[need]))[inv, lane[on]]
    words = corpus.word_ids[toks]
    rows = np.asarray(phi[jnp.asarray(words)]).astype(np.int64)
    want = np.empty(len(toks), np.int32)
    counts, topics = reference.ell_order(theta)
    for a in range(0, len(toks), single.TOKEN_BLOCK):
        b = slice(a, a + single.TOKEN_BLOCK)
        ps = reference.pstar(rows[b], phi_sum, float(cfg["beta"]), V, dtype)
        want[b] = reference.draw(ps, counts[local[b]], topics[local[b]],
                                 u[b, 0], u[b, 1], float(cfg["alpha"]),
                                 dtype)
    return want, got


def check(st, ctx) -> list[tuple[str, float]]:
    """The numbers compared, by name; ``ctx.limits`` holds their limits."""
    import jax
    import jax.numpy as jnp

    corpus, layout, cfg = st["corpus"], st["layout"], st["cfg"]
    K, V = int(cfg["num_topics"]), corpus.num_words
    layout_errors, _, count_errors = st.setdefault(
        "device_checks", single._device_checks())
    bad_layout = int(layout_errors(
        jnp.asarray(corpus.word_ids), jnp.asarray(corpus.doc_ids),
        jnp.asarray(layout["token_uid"]), jnp.asarray(layout["token_mask"]),
        jnp.asarray(layout["tile_word"]), jnp.asarray(layout["token_doc"])))
    if bad_layout:
        return [("layout_errors", bad_layout)]
    counts, invalid = _recount()(jnp.asarray(corpus.word_ids),
                                 jnp.asarray(canonical(st, st["z_end"])),
                                 V, K)
    phi, phi_sum = st["phi_end"], st["phi_sum_end"]
    sums = {s.device: s.data for s in phi_sum.addressable_shards}
    per_chip = {}
    for s in phi.addressable_shards:
        mine = jax.device_put(counts, s.device)
        per_chip[s.device] = int(count_errors(mine, s.data, sums[s.device]))
        del mine
    bad_counts = int(invalid) + per_chip[phi.addressable_shards[0].device]
    bad_replicas = sum(per_chip.values())
    del counts
    want, got = reference_draws(st, ctx.seed, np.float64)
    return [("layout_errors", bad_layout),
            ("count_errors", bad_counts),
            ("replica_errors", bad_replicas),
            ("draw_mismatch_share", float((want != got).mean()))]


def control(st, ctx) -> list[tuple[str, float]]:
    """The control: the reference in bfloat16 in the program's place."""
    import ml_dtypes

    want, _ = reference_draws(st, ctx.seed, np.float64)
    low, _ = reference_draws(st, ctx.seed, ml_dtypes.bfloat16)
    return [("draw_mismatch_share", float((want != low).mean()))]


def work_counts(st) -> dict:
    """The mean chip's share of one iteration's Table 1 counts (the whole
    corpus's over the number of chips), and the phi sync's payload."""
    whole = single.work_counts(st)
    n = st["chips"]
    out = {k: (whole[k][0] / n, whole[k][1] / n)
           for k in ("sampler", "plan", "count_update")}
    cfg = st["cfg"]
    out["mean_kd_per_token"] = whole["mean_kd_per_token"]
    out["sync"] = {"payload_bytes": lda_sync.payload_bytes(
                       int(cfg["num_words"]), int(cfg["num_topics"])),
                   "chips": n, "device_kind": st["kind"]}
    return out
