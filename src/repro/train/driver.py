"""Unified training driver — single-host and mesh behind one ``fit()``.

Before this module existed the repo had two hand-rolled drivers with
divergent surfaces: ``core.trainer.train`` (single device; ``TrainResult``
with the LL trajectory, tokens/sec and AOT compile time; ``obs=`` /
``metrics_out=`` / ``sanitize=`` / ``callback=``) and a manual loop around
``DistributedLDA.step`` in ``launch/train.py`` (mesh; checkpoint/resume; no
result object).  ``fit`` dispatches on ``mesh=`` and gives both paths the
whole surface:

  * the same per-iteration telemetry (``sample``/``eval`` host spans, one
    JSONL row per iteration) — all host-side, so draws are bit-identical
    to an uninstrumented run;
  * the same AOT-compile accounting (``TrainResult.compile_sec`` excluded
    from ``tokens_per_sec``, mesh path included via
    ``DistributedLDA.compile_step``);
  * the same checkpoint/resume protocol (canonical-z checkpoints keyed by
    corpus fingerprint; elastic across device count and partition mode);
  * the one resolved config (``ell_capacity`` filled exactly once, by
    ``trainer.resolve_config`` here or by ``DistributedLDA.__init__``)
    surfaced on ``TrainResult.cfg`` for reproducibility.

``trainer.train`` is now a deprecated alias for the single-host path.
"""
from __future__ import annotations

import functools
import time
from typing import Callable

import jax

from repro.analysis.runtime import sanitize_guards
from repro.core import trainer
from repro.core.corpus import Corpus, TiledCorpusShard, tile_corpus
from repro.core.trainer import LDAConfig, LDAState, TrainResult


def fit(
    corpus: Corpus,
    cfg: LDAConfig,
    num_iterations: int,
    mesh=None,                     # jax Mesh -> DistributedLDA path
    *,
    mode: str = "1d",              # mesh partition: "1d" (paper) | "2d"
    doc_axes=None,
    word_axes=("model",),
    eval_every: int = 1,
    shard: TiledCorpusShard | None = None,   # single-host: pre-tiled corpus
    callback: Callable[[int, LDAState, float], None] | None = None,
    obs=None,                      # repro.obs.Observability
    metrics_out: str | None = None,  # per-iteration JSONL sink path
    sanitize: bool = False,        # transfer-guard the sampling hot path
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 0,     # iterations between checkpoints (0 = off)
    resume: bool = True,           # resume from checkpoint_dir if compatible
    verbose: bool = False,         # print per-eval progress lines
) -> TrainResult:
    """Train LDA end to end; THE entry point for every driver.

    ``mesh=None`` runs the single-host path; passing a ``jax.sharding.Mesh``
    builds a ``DistributedLDA`` partition (``mode``/``doc_axes``/
    ``word_axes`` as in its constructor) and runs the same loop over the
    mesh step — every ``LDAConfig`` knob, ``sampler="pallas"`` included,
    works identically on both.  Telemetry, checkpointing and the returned
    ``TrainResult`` are path-independent.
    """
    if mesh is None:
        return _fit_single(corpus, cfg, num_iterations, eval_every=eval_every,
                           shard=shard, callback=callback, obs=obs,
                           metrics_out=metrics_out, sanitize=sanitize,
                           checkpoint_dir=checkpoint_dir,
                           checkpoint_every=checkpoint_every, resume=resume,
                           verbose=verbose)
    return _fit_mesh(corpus, cfg, num_iterations, mesh, mode=mode,
                     doc_axes=doc_axes, word_axes=word_axes,
                     eval_every=eval_every, callback=callback, obs=obs,
                     metrics_out=metrics_out, sanitize=sanitize,
                     checkpoint_dir=checkpoint_dir,
                     checkpoint_every=checkpoint_every, resume=resume,
                     verbose=verbose)


def _fit_single(corpus, cfg, num_iterations, *, eval_every, shard, callback,
                obs, metrics_out, sanitize, checkpoint_dir, checkpoint_every,
                resume, verbose) -> TrainResult:
    from repro.distributed import checkpoint as ckpt

    cfg = trainer.resolve_config(cfg, corpus)
    if shard is None:
        shard = tile_corpus(corpus, 1, cfg.tile_tokens)[0]

    mgr = fp = None
    if checkpoint_dir:
        mgr = ckpt.CheckpointManager(checkpoint_dir)
        fp = ckpt.corpus_fingerprint(corpus)

    key = jax.random.key(cfg.seed)
    it0, state = 0, None
    if mgr is not None and resume:
        latest = mgr.latest()
        if latest and latest[2].get("fingerprint") == fp:
            it0, z, _ = latest
            z_tiled = ckpt.scatter_canonical_z(z, shard.token_uid)
            state = trainer.state_from_z(
                cfg, shard, jax.numpy.asarray(z_tiled).astype(cfg.topic_dtype),
                it0)
            print(f"[resume] iteration {it0} (single-host)")
    if state is None:
        state = trainer.init_state(cfg, shard, key)

    def compile_step(tracer):
        # AOT-compile before the loop: iteration 0 used to include jit
        # compile time, polluting the first row of every throughput
        # trajectory.  Compile is reported separately instead.
        # The shard is an argument, not a closure: closed-over arrays would
        # be baked into the program as constants (hundreds of MB at corpus
        # scale), slowing the compile and bloating the compile cache.
        t0 = time.perf_counter()
        with tracer.span("compile", sampler=cfg.sampler):
            compiled = jax.jit(functools.partial(trainer.lda_iteration, cfg)
                               ).lower(shard, state, key).compile()
        return (lambda st: compiled(shard, st, key)), time.perf_counter() - t0

    ll_jit = functools.partial(
        jax.jit(functools.partial(trainer.log_likelihood, cfg)), shard)

    def save_fn(it, st):
        z = ckpt.gather_canonical_z(st.z, shard.token_uid, corpus.num_tokens)
        mgr.save(it + 1, z, {"fingerprint": fp, "mode": "single",
                             "num_topics": cfg.num_topics})

    return _run_loop(
        cfg, it0, num_iterations, state, compile_step,
        ll_fn=lambda st: float(ll_jit(st)) / corpus.num_tokens,
        save_fn=save_fn if mgr is not None else None,
        num_tokens=shard.num_tokens, mgr=mgr, eval_every=eval_every,
        callback=callback, obs=obs, metrics_out=metrics_out,
        sanitize=sanitize, checkpoint_every=checkpoint_every,
        verbose=verbose)


def _fit_mesh(corpus, cfg, num_iterations, mesh, *, mode, doc_axes,
              word_axes, eval_every, callback, obs, metrics_out, sanitize,
              checkpoint_dir, checkpoint_every, resume, verbose
              ) -> TrainResult:
    from repro.distributed import checkpoint as ckpt
    from repro.distributed.partition import DistributedLDA

    dl = DistributedLDA(cfg, mesh, corpus, mode=mode, doc_axes=doc_axes,
                        word_axes=word_axes)
    cfg = dl.cfg   # the one resolved config (ell_capacity filled)

    mgr = fp = None
    if checkpoint_dir:
        mgr = ckpt.CheckpointManager(checkpoint_dir)
        fp = ckpt.corpus_fingerprint(corpus)

    it0, state = 0, None
    if mgr is not None and resume:
        latest = mgr.latest()
        if latest and latest[2].get("fingerprint") == fp:
            it0, z, _ = latest
            state = dl.restore(z, it0)
            n_dev = len(mesh.devices.reshape(-1))
            print(f"[resume] iteration {it0} on {n_dev} devices ({mode})")
    if state is None:
        state = dl.init()

    def compile_step(tracer):
        with tracer.span("compile", sampler=cfg.sampler):
            step, compile_sec = dl.compile_step()
        return step, compile_sec

    return _run_loop(
        cfg, it0, num_iterations, state, compile_step,
        ll_fn=dl.log_likelihood,   # already per-token
        save_fn=(lambda it, st: dl.save_checkpoint(mgr, st,
                                                   {"fingerprint": fp}))
        if mgr is not None else None,
        num_tokens=corpus.num_tokens, mgr=mgr, eval_every=eval_every,
        callback=callback, obs=obs, metrics_out=metrics_out,
        sanitize=sanitize, checkpoint_every=checkpoint_every,
        verbose=verbose, first_row={"partition": dl.counters})


def _run_loop(cfg, it0, num_iterations, state, compile_step, *, ll_fn,
              save_fn, num_tokens, mgr, eval_every, callback, obs,
              metrics_out, sanitize, checkpoint_every, verbose,
              first_row=None) -> TrainResult:
    """The one training loop both paths share.

    Telemetry is host-side only (``repro.obs``): ``sample``/``eval`` phase
    spans in ``obs.tracer`` (device-side phase names come from the
    ``jax.named_scope`` annotations inside ``lda_iteration``) and — when
    ``metrics_out`` is given — one JSONL row per iteration.  None of it
    touches keys or traced values, so draws are bit-identical to an
    uninstrumented run (pinned in tests/test_obs.py).  ``first_row``
    (the mesh partition's counters) is written into the first row only.
    """
    from repro.obs import JsonlSink, NULL_SINK, NULL_TRACER

    tracer = obs.tracer if obs is not None else NULL_TRACER
    sink = JsonlSink(metrics_out) if metrics_out else NULL_SINK

    step, compile_sec = compile_step(tracer)

    lls: list[float] = []
    tps: list[float] = []
    st: list[tuple[float, float, float, float]] = []
    try:
        for it in range(it0, num_iterations):
            t0 = time.perf_counter()
            with tracer.span("sample", iteration=it):
                # under --sanitize any implicit host<->device transfer in
                # the sweep dispatch is an error (AOT compile + eval stay
                # outside the guard: they are allowed to stage host data)
                with sanitize_guards(sanitize):
                    state, stats = step(state)
                    state.z.block_until_ready()
            dt = time.perf_counter() - t0
            tps.append(num_tokens / dt)
            st.append((float(stats.sparse_frac), float(stats.ell_overflow),
                       float(stats.mean_s_over_sq),
                       float(stats.row_width_share)))
            ll = None
            if (it + 1) % eval_every == 0 or it == num_iterations - 1:
                with tracer.span("eval", iteration=it):
                    ll = float(ll_fn(state))
                lls.append(ll)
                if verbose:
                    print(f"iter {it + 1:5d}  {tps[-1] / 1e6:7.2f}M tok/s  "
                          f"LL/token {ll:.4f}  "
                          f"sparse {st[-1][0]:.2f}  "
                          f"W/P {st[-1][3]:.2f}  "
                          f"S/(S+Q) {st[-1][2]:.2f}")
                if callback:
                    callback(it, state, ll)
            sink.write(dict(iteration=it, seconds=dt,
                            tokens=num_tokens, tokens_per_sec=tps[-1],
                            sparse_frac=st[-1][0],
                            row_width_share=st[-1][3], ell_overflow=st[-1][1],
                            mean_s_over_sq=st[-1][2], ll_per_token=ll,
                            **(first_row if it == it0 and first_row else {})))
            if (save_fn is not None and checkpoint_every
                    and (it + 1) % checkpoint_every == 0):
                save_fn(it, state)
    finally:
        sink.close()
    if mgr is not None:
        mgr.wait()
    return TrainResult(state=state, ll_per_token=lls, tokens_per_sec=tps,
                       stats=st, compile_sec=compile_sec, cfg=cfg)
