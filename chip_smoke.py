"""Smoke run of the main path on TPU: LDA training, then fold-in serving.

    python chip_smoke.py              # one chip, NYTimes width
    python chip_smoke.py --chips 4    # the 4-chip paths only

One chip: a seeded NYTimes-shaped corpus at the full vocabulary
(V=101,636) and K=1024 topics, cut in depth only (documents and tokens), is
trained through ``repro.train.fit`` with the fused Pallas sampler and again
with the XLA ``sq`` sampler under the same key.  The trained state is
published as a serving snapshot, loaded back, and a few dozen NYTimes-length
documents are served through ``LDAServeEngine`` with the Pallas fold-in and
again with the XLA fold-in under the same seed.  The run fails unless the
log-likelihood per token is finite and improves, every request is answered,
the two fold-ins agree exactly, and both compiled programs hold Mosaic
kernels (``tpu_custom_call``), i.e. the kernels ran compiled, not
interpreted.

``--chips 4`` runs only what exists across chips: ``fit`` on a 1d mesh of
four chips (Pallas against ``sq``) and the snapshot served V-sharded over
four chips (``psum`` and ``all2all`` row assembly) against single-chip
serving.

Earlier lines report what happened (compile seconds, LL per iteration,
draw agreement); none of it is a benchmark.  The last line is one JSON
object naming the device.  Without a TPU the script exits non-zero and
prints no result; it never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

# synthetic.nytimes_like keeps the full vocabulary from this scale up
MIN_SCALE = 0.05


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def train_phase(corpus, num_topics: int, iters: int, mesh=None) -> dict:
    """``fit`` with ``sampler="pallas"`` then ``"sq"`` under the same key.

    Returns ``{sampler: TrainResult}`` plus the fraction of draws on which
    the two samplers agree after the first iteration and after the last
    (padding slots never move, so disagreements are counted over real
    tokens).  Raises ``SmokeFailure`` unless LL/token is finite and improves
    from the first iteration to the last for both samplers."""
    import numpy as np

    from repro.core.trainer import LDAConfig
    from repro.train import fit

    out, first_z = {}, {}
    for sampler in ("pallas", "sq"):
        cfg = LDAConfig(num_topics=num_topics, sampler=sampler)

        def keep_first(it, state, ll, sampler=sampler):
            if it == 0:
                first_z[sampler] = np.asarray(state.z)

        res = fit(corpus, cfg, iters, mesh, mode="1d", word_axes=(),
                  callback=keep_first)
        lls = res.ll_per_token
        log(f"[train] {sampler}: compile {res.compile_sec:.1f} s; LL/token "
            f"by iteration {[round(x, 4) for x in lls]}; sampled tokens/s "
            f"{[round(x) for x in res.tokens_per_sec]} (not a benchmark)")
        if not all(math.isfinite(x) for x in lls):
            raise SmokeFailure(f"{sampler}: LL/token not finite: {lls}")
        if not lls[-1] > lls[0]:
            raise SmokeFailure(f"{sampler}: LL/token did not improve: {lls}")
        out[sampler] = res

    def agree(a, b) -> float:
        return 1.0 - float((np.asarray(a) != np.asarray(b)).sum()
                           ) / corpus.num_tokens

    out["agree_first"] = agree(first_z["pallas"], first_z["sq"])
    out["agree_last"] = agree(out["pallas"].state.z, out["sq"].state.z)
    log(f"[train] pallas/sq draws agree on {out['agree_first']:.6f} of tokens "
        f"after iteration 1 and {out['agree_last']:.6f} after iteration "
        f"{iters}")
    return out


def serving_docs(num_words: int, n: int, seed: int) -> list:
    """``n`` unseen NYTimes-shaped documents (Zipf word ids, ~332 tokens)."""
    from repro.data.synthetic import zipf_corpus

    c = zipf_corpus(num_docs=n, num_words=num_words, avg_doc_len=332,
                    seed=seed)
    ends = c.doc_lengths().cumsum()
    return [c.word_ids[e - m:e] for e, m in zip(ends, c.doc_lengths())]


def serve(snapshot, docs, impl: str, seed: int, length: int,
          comm: str = "auto") -> list:
    """Serve ``docs`` through ``LDAServeEngine`` as one batch; returns the
    per-request results.  Raises ``SmokeFailure`` unless all are answered."""
    from repro.serve.engine import EngineConfig, LDAServeEngine
    from repro.serve.infer import InferConfig
    from repro.serve.snapshot import HotSwapModel

    cfg = EngineConfig(max_batch=len(docs), max_delay_ms=5000.0,
                       length_buckets=(length,), max_queue=len(docs),
                       infer=InferConfig(impl=impl, comm=comm))
    engine = LDAServeEngine(HotSwapModel(snapshot), cfg, seed=seed)
    try:
        t0 = time.perf_counter()
        results = engine.infer_many(docs, timeout=900.0)
        dt = time.perf_counter() - t0
    finally:
        engine.stop()
    answered = sum("theta" in r for r in results)
    log(f"[serve] {impl} (comm {comm}): {answered}/{len(docs)} requests "
        f"answered in {dt:.1f} s, compile included (not a benchmark)")
    if answered != len(docs):
        raise SmokeFailure(f"{impl}: {len(docs) - answered} requests "
                           f"unanswered")
    return results


def same_results(a: list, b: list, what: str) -> None:
    """The fold-in parity contract: identical theta and top-k topics."""
    import numpy as np

    for i, (x, y) in enumerate(zip(a, b)):
        if not (np.array_equal(x["theta"], y["theta"])
                and np.array_equal(x["top_topics"], y["top_topics"])):
            diff = float(np.abs(np.asarray(x["theta"])
                                - np.asarray(y["theta"])).max())
            raise SmokeFailure(f"{what}: request {i} differs "
                               f"(max |d theta| {diff:.3g})")
    log(f"[serve] {what}: theta and top-k identical for all {len(a)} "
        f"requests")


def publish(state, corpus, cfg, workdir: str) -> str:
    """Publish the trained state as a serving snapshot; returns its path."""
    from repro.distributed.checkpoint import CheckpointManager

    mgr = CheckpointManager(workdir)
    return mgr.publish_snapshot(state, cfg.resolved_alpha(), cfg.beta,
                                num_words_total=corpus.num_words)


def kernels_compiled(corpus, cfg, docs_shape) -> None:
    """Raise unless the compiled training step and fold-in hold Mosaic
    kernels (``tpu_custom_call``): proof the kernels ran compiled."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import trainer
    from repro.core.corpus import tile_corpus
    from repro.serve import infer

    cfg = trainer.resolve_config(cfg, corpus)
    shard = tile_corpus(corpus, 1, cfg.tile_tokens)[0]
    key = jax.random.key(cfg.seed)
    state = jax.eval_shape(functools.partial(trainer.init_state, cfg),
                           shard, key)
    step = jax.jit(functools.partial(trainer.lda_iteration, cfg)
                   ).lower(shard, state, key).compile().as_text()
    B, L = docs_shape
    K, V = cfg.num_topics, corpus.num_words
    fold = infer.fold_in.lower(
        jax.ShapeDtypeStruct((V, K), jnp.int32),
        jax.ShapeDtypeStruct((K,), jnp.int32),
        jax.ShapeDtypeStruct((B, L), jnp.int32),
        jax.ShapeDtypeStruct((B, L), jnp.bool_), key, 0.05, 0.01,
        num_words_total=V, impl="pallas").compile().as_text()
    for name, text in (("training step", step), ("fold-in", fold)):
        n = text.count("tpu_custom_call")
        log(f"[kernels] compiled {name}: {n} tpu_custom_call sites")
        if not n:
            raise SmokeFailure(f"compiled {name} holds no Mosaic kernel")


def nytimes_corpus(scale: float, seed: int, chips: int):
    """The NYTimes-shaped corpus, cut in depth only.  Raises
    ``SmokeFailure`` unless V is NYTimes' own 101,636."""
    from repro.configs import lda_nytimes

    full = lda_nytimes.FULL
    corpus = lda_nytimes.scaled(scale, seed)
    log(f"[corpus] NYTimes shape at scale {scale} over {chips} chip(s): "
        f"V={corpus.num_words}, K={lda_nytimes.CONFIG.num_topics}; "
        f"{corpus.num_docs} docs ({corpus.num_docs / full['num_docs']:.3f} "
        f"of {full['num_docs']}) and {corpus.num_tokens} tokens "
        f"({corpus.num_tokens / full['num_tokens']:.3f} of "
        f"{full['num_tokens']})")
    if corpus.num_words != full["num_words"]:
        raise SmokeFailure(f"V={corpus.num_words} is not NYTimes' "
                           f"{full['num_words']}")
    return corpus, lda_nytimes.CONFIG.num_topics


def one_chip(args) -> None:
    corpus, K = nytimes_corpus(args.scale, args.seed, 1)
    trained = train_phase(corpus, K, args.iters)
    res = trained["pallas"]

    docs = serving_docs(corpus.num_words, args.requests, args.seed + 1)
    length = max(512, 1 << (max(map(len, docs)) - 1).bit_length())
    with tempfile.TemporaryDirectory() as workdir:
        from repro.serve.snapshot import load_snapshot

        path = publish(res.state, corpus, res.cfg, workdir)
        snap = load_snapshot(path)
        log(f"[serve] snapshot {os.path.basename(path)}: phi "
            f"{tuple(snap.phi_vk.shape)}; {len(docs)} requests of "
            f"{min(map(len, docs))}-{max(map(len, docs))} tokens, bucket "
            f"{length}")
        got = serve(snap, docs, "pallas", args.seed, length)
        want = serve(snap, docs, "xla", args.seed, length)
        same_results(got, want, "pallas vs xla fold-in")
    kernels_compiled(corpus, res.cfg, (len(docs), length))


def four_chips(args) -> None:
    import jax

    from repro.launch.mesh import make_mesh
    from repro.serve.snapshot import load_any_snapshot

    n = len(jax.devices())
    corpus, K = nytimes_corpus(args.scale, args.seed, n)
    mesh = make_mesh((n,), ("data",))
    trained = train_phase(corpus, K, args.iters, mesh)
    res = trained["pallas"]

    docs = serving_docs(corpus.num_words, args.requests, args.seed + 1)
    length = max(512, 1 << (max(map(len, docs)) - 1).bit_length())
    with tempfile.TemporaryDirectory() as workdir:
        path = publish(res.state, corpus, res.cfg, workdir)
        single = serve(load_any_snapshot(path), docs, "pallas", args.seed,
                       length)
        for comm in ("psum", "all2all"):
            sharded = load_any_snapshot(path, shards=n, comm=comm)
            got = serve(sharded, docs, "pallas", args.seed, length, comm=comm)
            same_results(got, single,
                         f"{n}-shard ({comm}) vs single-chip serving")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--scale", type=float, default=MIN_SCALE,
                    help=f"NYTimes depth kept (docs and tokens), at least "
                         f"{MIN_SCALE}, where V is still the full 101,636")
    # two iterations: the XLA sq sweep, the reference here, samples about
    # 32k tokens/s on one v5e at this shape (154 s per iteration at scale
    # 0.05), so each extra iteration costs minutes of the time limit
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.scale < MIN_SCALE:
        ap.error(f"--scale {args.scale} cuts V below NYTimes' width; "
                 f"use at least {MIN_SCALE}")

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        from repro.launch.cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache = use_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devices)} "
              f"{dev.platform} device(s); this check never falls back to "
              f"the CPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    log(f"[device] {len(devices)} x {dev.device_kind} ({dev.platform}); "
        f"compile cache {cache}")

    t0 = time.perf_counter()
    try:
        (one_chip if args.chips == 1 else four_chips)(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"[done] {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
