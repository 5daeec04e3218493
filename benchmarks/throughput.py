"""Paper Table 4 / Fig 7: sampling throughput (#Tokens/sec, Eq. 2).

One row per training sampler backend — ``dense`` (the O(K) baseline the
paper improves on), ``sq`` (the sparsity-aware S/Q sampler as an XLA scan)
and ``pallas`` (the fused ``repro.kernels.lda_sample`` sweep; off-TPU it
times the *interpreter*, validating the path end to end — the on-chip win
is a hardware number).  Timings are of the AOT-compiled iteration only
(compile time never pollutes a row; see ``repro.train.fit``).

The sweep ends with an ``obs_overhead_training`` row — the measured
observer effect of the ``repro.obs`` instrumentation on the training loop:
``trainer.train`` with the real registry + tracer vs the no-op bundle,
alternating runs, per-iteration medians compared (compile time excluded on
both sides).  The row asserts the overhead stays under 2%.

``--json PATH`` records every row as JSON in the shared BENCH schema
(``common.write_bench_json``) — the CI bench-smoke job uploads it as
``BENCH_training.json``, the training-side twin of ``BENCH_serving.json``
(same envelope, asserted by CI); ``--tiny`` shrinks the corpus to a
seconds-scale CI config.
"""
import functools

from .common import emit, timeit, write_bench_json

SAMPLERS = ("dense", "sq", "pallas")

_ROWS: list | None = None   # row recorder for --json


def _emit(name: str, us: float, derived: str, **extra):
    emit(name, us, derived)
    if _ROWS is not None:
        _ROWS.append(dict(name=name, us_per_call=round(us, 1),
                          derived=derived, **extra))


def _mesh_rows(tiny):
    """Mesh-sharded sweep rows: sq + pallas on a 1d data mesh over every
    visible device, and the pallas overlapped-sync schedule vs the
    serialized one.

    Timings alternate the two sync schedules and compare per-iteration
    medians (same discipline as ``_obs_overhead_row``) so the
    ``overlap_speedup`` field is a paired measurement, not two noisy
    one-shots; a sub-1.0 first reading is retried at higher repeats before
    being recorded."""
    import dataclasses

    import jax
    from repro.core import trainer
    from repro.data.synthetic import zipf_corpus
    from repro.distributed.partition import DistributedLDA

    n_dev = len(jax.devices())
    if n_dev < 2:
        return
    corpus = zipf_corpus(num_docs=96, num_words=160, avg_doc_len=40, seed=0)
    K = 128
    mesh = jax.make_mesh((n_dev,), ("data",))
    base = trainer.LDAConfig(num_topics=K, tile_tokens=64, tiles_per_step=32,
                             micro_chunks=2)

    def bench(cfg, iters):
        dl = DistributedLDA(cfg, mesh, corpus, mode="1d",
                            doc_axes=("data",), word_axes=())
        step, _ = dl.compile_step()
        state = dl.init()
        return timeit(lambda: step(state)[0].z.block_until_ready(),
                      warmup=1, iters=iters)

    iters = 2 if tiny else 3
    us_sq = bench(dataclasses.replace(base, sampler="sq"), iters)
    _emit(f"train_mesh1d{n_dev}_sq_K{K}", us_sq,
          f"tokens_per_sec={corpus.num_tokens / (us_sq / 1e6):.3g}",
          sampler="sq", shards=n_dev,
          tokens_per_sec=corpus.num_tokens / (us_sq / 1e6),
          num_tokens=corpus.num_tokens)

    cfg_pl = dataclasses.replace(base, sampler="pallas")
    cfg_ov = dataclasses.replace(base, sampler="pallas", sync_overlap=True)

    def measure(repeats):
        plain, over = [], []
        for _ in range(repeats):
            plain.append(bench(cfg_pl, iters))
            over.append(bench(cfg_ov, iters))
        plain.sort()
        over.sort()
        return plain[len(plain) // 2], over[len(over) // 2]

    us_pl, us_ov = measure(2 if tiny else 3)
    if us_ov > us_pl:    # retry once at higher repeats before recording <1x
        us_pl, us_ov = measure(4)
    for label, us, extra in (
            ("", us_pl, {}),
            ("_overlap", us_ov, dict(overlap_speedup=round(us_pl / us_ov,
                                                           3))),
    ):
        tps = corpus.num_tokens / (us / 1e6)
        _emit(f"train_mesh1d{n_dev}_pallas{label}_K{K}", us,
              f"tokens_per_sec={tps:.3g}"
              + (f";overlap_speedup={us_pl / us_ov:.3f}" if label else ""),
              sampler="pallas", shards=n_dev, tokens_per_sec=tps,
              num_tokens=corpus.num_tokens, **extra)


def _obs_overhead_row(tiny):
    """Instrumented vs no-op ``repro.train.fit``, per-iteration medians.

    ``paired_overhead_pct`` times whole calls; here each ``fit`` call
    re-AOT-compiles, so we instead compare the *per-iteration* medians the
    trainer itself reports (its timing loop starts after compile) — the
    alternation discipline is the same.
    """
    from repro.core import trainer
    from repro.core.corpus import ell_capacity
    from repro.data.synthetic import zipf_corpus
    from repro.obs import Observability
    from repro.train import fit

    # big enough that one iteration is ~10ms+ of sampling — the per-iteration
    # instrumentation tax is fixed µs-scale, so a too-small corpus would
    # inflate the ratio into pure noise
    corpus = zipf_corpus(num_docs=192, num_words=160, avg_doc_len=48, seed=1)
    K = 64
    cfg = trainer.LDAConfig(num_topics=K, tile_tokens=64, tiles_per_step=8,
                            ell_capacity=ell_capacity(corpus, K))
    iters = 6 if tiny else 10

    def iter_s(obs):
        res = fit(corpus, cfg, iters, eval_every=iters, obs=obs)
        med_tps = sorted(res.tokens_per_sec)[iters // 2]
        return corpus.num_tokens / med_tps

    def measure(repeats):
        base, inst = [], []
        for _ in range(repeats):
            base.append(iter_s(Observability.noop()))
            inst.append(iter_s(Observability.default(trace=True)))
        base.sort()
        inst.sort()
        mb, mi = base[len(base) // 2], inst[len(inst) // 2]
        return max(0.0, (mi - mb) / mb * 100.0), mb, mi

    iter_s(Observability.noop())     # warm any lazy imports outside timing
    pct, mb, mi = measure(3 if tiny else 5)
    if pct >= 2.0:   # one retry at higher repeats before declaring a regression
        pct, mb, mi = measure(7)
    _emit("obs_overhead_training", mi * 1e6,
          f"overhead_pct={pct:.2f} baseline_iter_ms={mb * 1e3:.2f}",
          overhead_pct=round(pct, 2), baseline_iter_ms=round(mb * 1e3, 3))
    assert pct < 2.0, f"observer effect {pct:.2f}% >= 2% on the training loop"


def run(samplers=SAMPLERS, tiny=False):
    import jax
    from repro.core import trainer
    from repro.core.corpus import ell_capacity, tile_corpus
    from repro.data.synthetic import zipf_corpus

    # paper regime: K >> avg doc length (sparsity pays), T/V >~ 100 so the
    # per-word p*/tree work amortizes over that word's tokens.  The pallas
    # row always times the interpret-mode kernel off-TPU, so it gets the
    # tiny corpus in every mode (same config => rows stay comparable to the
    # BENCH_training.json trajectory).
    big = zipf_corpus(num_docs=512, num_words=500, avg_doc_len=100, seed=0)
    small = zipf_corpus(num_docs=96, num_words=160, avg_doc_len=40, seed=0)
    on_tpu = jax.default_backend() == "tpu"
    for which in samplers:
        corpus = small if (tiny or (which == "pallas" and not on_tpu)) else big
        K = 128 if corpus is small else 1024
        cfg = trainer.LDAConfig(num_topics=K, tile_tokens=64,
                                tiles_per_step=8 if which == "dense" else 32,
                                sampler=which,
                                ell_capacity=ell_capacity(corpus, K))
        shard = tile_corpus(corpus, 1, cfg.tile_tokens)[0]
        key = jax.random.key(0)
        state = trainer.init_state(cfg, shard, key)
        step = jax.jit(functools.partial(trainer.lda_iteration, cfg, shard))
        compiled = step.lower(state, key).compile()
        iters = 1 if (which == "pallas" and not on_tpu) else 3
        us = timeit(lambda: compiled(state, key)[0].z, warmup=1, iters=iters)
        tps = corpus.num_tokens / (us / 1e6)
        _emit(f"train_{which}_K{K}", us,
              f"tokens_per_sec={tps:.3g};T={corpus.num_tokens}",
              sampler=which, tokens_per_sec=tps, num_tokens=corpus.num_tokens)


    # mesh-sharded sweep (sq + pallas, overlapped vs serialized sync) —
    # skipped silently on single-device hosts
    _mesh_rows(tiny)

    # measured observer effect of the repro.obs instrumentation
    _obs_overhead_row(tiny)


def main(argv=None) -> int:
    """Standalone entry: ``python -m benchmarks.throughput --tiny --json ...``."""
    import argparse

    global _ROWS

    ap = argparse.ArgumentParser()
    ap.add_argument("--sampler", nargs="+", choices=SAMPLERS,
                    default=list(SAMPLERS),
                    help="training sampler backend(s) to time")
    ap.add_argument("--tiny", action="store_true",
                    help="seconds-scale sweep for the CI bench-smoke job")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write every row as JSON (CI artifact)")
    args = ap.parse_args(argv)
    if args.json:
        _ROWS = []
    print("name,us_per_call,derived")
    run(samplers=tuple(args.sampler), tiny=args.tiny)
    if args.json:
        write_bench_json(args.json, "training_throughput", _ROWS,
                         tiny=args.tiny)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
