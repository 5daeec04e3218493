"""LDA serving launcher: online topic inference against a frozen snapshot.

The paper's motivating scenario — "slow LDA may prevent the usage of LDA in
many scenarios, e.g., online service" — closed end to end: a trained model is
published as a snapshot (repro.serve.snapshot), and this process answers
per-document topic queries through the micro-batching engine with hot-swap.

Self-driving benchmark (trains a tiny synthetic model if the snapshot is
missing, serves a request storm, hot-swaps a fresher snapshot mid-flight):

    PYTHONPATH=src python -m repro.launch.serve_lda --snapshot /tmp/lda.npz --bench

HTTP JSON endpoint (stdlib only):

    PYTHONPATH=src python -m repro.launch.serve_lda --snapshot /tmp/lda.npz --port 8080
    POST /infer  {"tokens": [3, 17, ...], "deadline_ms": 250}
                 -> theta + top topics; 429 + structured reason when
                    admission control rejects (full queue, blown deadline)
    POST /swap   {"snapshot": "/path/to/newer.npz"}  -> hot-swap, no restart
    GET  /metrics    -> Prometheus text exposition (repro.obs registry)
    GET  /stats      -> engine stats + queue depth, jit cache, device memory
    GET  /trace      -> Chrome trace JSON of the serving phase spans
    GET  /healthz    -> 200 when ready; 503 (with reasons) when stopped,
                        saturated, or a worker thread is dead

Robustness knobs: ``--max-queue`` bounds the admission queue,
``--admission`` picks the overload policy (block/reject/shed_oldest),
``--deadline-ms`` sets the default per-request deadline, and
``--fault-plan`` injects deterministic faults (chaos testing — see
repro.serve.faults for the spec grammar).

``--trace-out`` / ``--metrics-out`` additionally write the trace JSON and a
final metrics dump at shutdown (bench mode: after the storm).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--snapshot", required=True, help="snapshot .npz path")
    ap.add_argument("--bench", action="store_true",
                    help="self-drive: train-if-missing, storm, hot-swap demo")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    # engine knobs
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--delay-ms", type=float, default=3.0)
    ap.add_argument("--length-buckets", type=int, nargs="+",
                    default=[32, 64, 128, 256])
    # robustness knobs
    ap.add_argument("--max-queue", type=int, default=256,
                    help="bounded admission queue depth (0 = unbounded)")
    ap.add_argument("--admission", choices=("block", "reject", "shed_oldest"),
                    default="block",
                    help="policy when the queue is full: backpressure the "
                         "submitter, 429 the request, or shed the oldest "
                         "queued request to admit the new one")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="default per-request deadline; expired requests "
                         "are dropped before device time is spent on them "
                         "(requests may override via the /infer payload)")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="deterministic fault injection: JSON list or "
                         "compact 'kind[@at][xcount][:delay_s]' items, e.g. "
                         "'device_oom@1,worker_exception@0x3' "
                         "(see repro.serve.faults)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for rate-based fault specs")
    ap.add_argument("--burn-in", type=int, default=8)
    ap.add_argument("--samples", type=int, default=4)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--impl", choices=("xla", "pallas"), default="xla",
                    help="fold-in implementation: the pure-XLA sweeps or "
                         "the Pallas kernel (repro.kernels.fold_in; "
                         "interpret mode on CPU) — draw-identical")
    ap.add_argument("--shards", type=int, default=0,
                    help="serve phi word-sharded over this many mesh "
                         "devices; a dense snapshot is re-split at load, a "
                         ".sharded directory keeps its own layout (0/1 = "
                         "unsharded)")
    ap.add_argument("--comm", choices=("auto", "psum", "all2all"),
                    default="auto",
                    help="V-sharded gather strategy: 'psum' assembles the "
                         "(B, L, K) rows with a full psum, 'all2all' routes "
                         "only the batch's token ids to the owning shards "
                         "and moves the gathered rows back (comm scales "
                         "with tokens, not B*L*K), 'auto' uses the "
                         "snapshot's own tag; draws are bit-identical "
                         "either way")
    # observability
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the serving phase-span trace (Chrome trace "
                         "JSON, Perfetto-loadable) at shutdown / bench end")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a final JSON dump of stats + the metrics "
                         "registry at shutdown / bench end")
    ap.add_argument("--no-trace", action="store_true",
                    help="disable phase-span recording (GET /trace returns "
                         "an empty trace; the bounded ring buffer is cheap, "
                         "so tracing is on by default)")
    ap.add_argument("--sanitize", action="store_true",
                    help="debug mode: jax.debug_nans, transfer-guard the "
                         "fold-in sweep, and runtime lock-held assertions "
                         "in the engine")
    # bench-mode training knobs
    ap.add_argument("--topics", type=int, default=32)
    ap.add_argument("--train-iters", type=int, default=25)
    ap.add_argument("--bench-docs", type=int, default=96)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def make_fault_plan(args):
    """One FaultPlan per process (shared by the loader, the hot-swap model
    and the engine, so per-site event counters stay globally consistent)."""
    spec = getattr(args, "fault_plan", None)
    if not spec:
        return None
    from repro.serve import FaultPlan

    return FaultPlan.parse(spec, seed=getattr(args, "fault_seed", 0))


def load_model(args, path: str | None = None, fault_plan=None):
    """Load the snapshot honoring --shards: dense files are re-split into
    word shards at load time, ``.sharded`` directories keep their layout."""
    from repro.serve import load_any_snapshot

    return load_any_snapshot(path or args.snapshot,
                             shards=max(args.shards, 0),
                             comm=None if args.comm == "auto" else args.comm,
                             fault_plan=fault_plan)


def make_engine(args, snap, fault_plan=None):
    from repro.obs import Observability
    from repro.serve import EngineConfig, HotSwapModel, InferConfig, LDAServeEngine

    sanitize = bool(getattr(args, "sanitize", False))
    if sanitize:
        from repro.analysis.runtime import enable_debug_nans
        enable_debug_nans()
    if fault_plan is None:
        fault_plan = make_fault_plan(args)
    model = HotSwapModel(snap, fault_plan=fault_plan)
    cfg = EngineConfig(
        max_batch=args.max_batch, max_delay_ms=args.delay_ms,
        length_buckets=tuple(args.length_buckets),
        infer=InferConfig(burn_in=args.burn_in, samples=args.samples,
                          top_k=args.top_k, impl=args.impl, comm=args.comm),
        max_queue=getattr(args, "max_queue", 256),
        admission=getattr(args, "admission", "block"),
        default_deadline_ms=getattr(args, "deadline_ms", None),
        fault_plan=fault_plan,
        sanitize=sanitize)
    obs = Observability.default(trace=not getattr(args, "no_trace", False))
    return model, LDAServeEngine(model, cfg, seed=args.seed, obs=obs)


def device_memory_stats() -> dict:
    """Per-device ``memory_stats()`` (bytes in use / limit); backends that
    don't expose it (CPU) report an empty dict per device."""
    import jax

    out = {}
    for d in jax.local_devices():
        try:
            out[str(d)] = d.memory_stats() or {}
        except Exception:
            out[str(d)] = {}
    return out


def enriched_stats(model, engine) -> dict:
    """``engine.stats()`` + serving context: model version/shape and device
    memory (queue depth + jit cache size are already in stats())."""
    snap = model.acquire()[1]
    s = engine.stats()
    s.update(model_version=model.version, num_words=snap.num_words,
             num_topics=snap.num_topics,
             device_memory=device_memory_stats())
    return s


def _dump_obs(args, model, engine):
    """Honor --trace-out / --metrics-out at shutdown or bench end."""
    if args.trace_out:
        print(f"[obs] trace -> {engine.obs.tracer.export(args.trace_out)}")
    if args.metrics_out:
        payload = dict(stats=enriched_stats(model, engine),
                       registry=engine.obs.registry.snapshot())
        with open(args.metrics_out, "w") as f:
            json.dump(payload, f, indent=1, default=str)
        print(f"[obs] metrics -> {args.metrics_out}")


# ---------------------------------------------------------------------------
# bench mode
# ---------------------------------------------------------------------------

def _train_and_export(args, extra_iters: int = 0):
    """Train the tiny synthetic model and export a snapshot to args.snapshot.

    Returns (corpus, cfg, train_result) so the hot-swap demo can keep
    training from the same corpus.
    """
    from repro.core import trainer
    from repro.data.synthetic import lda_corpus
    from repro.serve import save_snapshot, snapshot_from_state
    from repro.train import fit

    corpus = lda_corpus(num_docs=256, num_words=400,
                        num_topics=args.topics, avg_doc_len=64,
                        seed=args.seed)
    cfg = trainer.LDAConfig(num_topics=args.topics, tile_tokens=64,
                            tiles_per_step=16, seed=args.seed)
    res = fit(corpus, cfg, args.train_iters + extra_iters,
              eval_every=args.train_iters + extra_iters)
    snap = snapshot_from_state(res.state, cfg.resolved_alpha(), cfg.beta,
                               num_words_total=corpus.num_words)
    save_snapshot(args.snapshot, snap)
    return corpus, cfg, res


def run_bench(args) -> int:
    import numpy as np
    from repro.serve import ShardedModelSnapshot
    from repro.serve.eval import docs_from_corpus, heldout_perplexity

    if not os.path.exists(args.snapshot):
        print(f"[bench] no snapshot at {args.snapshot}; training "
              f"K={args.topics} synthetic model ({args.train_iters} iters)")
        t0 = time.perf_counter()
        _train_and_export(args)
        print(f"[bench] trained + exported in {time.perf_counter() - t0:.1f}s")
    snap = load_model(args)
    layout = (f"V-sharded x{snap.num_shards} (comm={snap.comm})"
              if isinstance(snap, ShardedModelSnapshot) else "dense")
    print(f"[bench] snapshot: V={snap.num_words} K={snap.num_topics} "
          f"iteration={snap.meta.get('iteration')} phi={layout}")

    # request storm: unseen synthetic docs with the same vocabulary
    from repro.data.synthetic import lda_corpus
    req_corpus = lda_corpus(num_docs=args.bench_docs,
                            num_words=snap.num_words,
                            num_topics=snap.num_topics, avg_doc_len=64,
                            seed=args.seed + 1)
    docs = docs_from_corpus(req_corpus)

    model, engine = make_engine(args, snap)
    print(f"[bench] fold-in impl: {args.impl}")
    engine.infer(docs[0])  # warm the bucket compiles outside the timed storm
    results = engine.infer_many(docs)
    stats = engine.stats()
    print(f"[bench] served {int(stats['requests'])} docs in "
          f"{stats['batches']:.0f} batches (mean batch "
          f"{stats['mean_batch']:.1f})")
    print(f"[bench] p50 {stats['p50_ms']:.1f} ms   p99 {stats['p99_ms']:.1f} ms"
          f"   {stats['docs_per_sec']:.1f} docs/sec")

    ppl = heldout_perplexity(snap, docs[: min(32, len(docs))])
    print(f"[bench] held-out document-completion perplexity: "
          f"{ppl.perplexity:.1f} over {ppl.num_tokens} tokens")

    # hot-swap: publish a further-trained snapshot; the engine keeps running
    print(f"[bench] training {args.train_iters + 15} iters for the v2 snapshot")
    _train_and_export(args, extra_iters=15)
    snap2 = load_model(args)   # --shards: the v2 model hot-swaps in sharded too
    v = model.publish(snap2)
    results2 = engine.infer_many(docs[:16])
    moved = max(float(np.abs(r2["theta"] - r1["theta"]).sum())
                for r1, r2 in zip(results[:16], results2))
    print(f"[bench] hot-swapped to model_version={v} without restart; "
          f"max |Δtheta|₁ across redone docs = {moved:.3f}")
    assert results2[0]["model_version"] == v
    print(f"[bench] sliding-window rate {stats['docs_per_sec_window']:.1f} "
          f"docs/sec (lifetime {stats['docs_per_sec']:.1f})")
    _dump_obs(args, model, engine)
    engine.stop()
    return 0


# ---------------------------------------------------------------------------
# HTTP mode (stdlib only — no framework deps)
# ---------------------------------------------------------------------------

def make_http_server(args, model, engine):
    """Build (not start) the ThreadingHTTPServer — separated from
    ``run_http`` so tests can bind port 0 and drive the real endpoints."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def _reply(self, code: int, obj):
            self._reply_raw(code, json.dumps(obj, default=str).encode(),
                            "application/json")

        def _reply_raw(self, code: int, body: bytes, ctype: str):
            self.send_response(code)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # quiet access log
            pass

        def do_GET(self):
            if self.path == "/healthz":
                health = engine.ready()
                code = 200 if health["ready"] else 503
                self._reply(code, {"ok": health["ready"],
                                   "model_version": model.version,
                                   **health})
            elif self.path == "/stats":
                self._reply(200, enriched_stats(model, engine))
            elif self.path == "/metrics":
                # Prometheus text exposition format 0.0.4
                self._reply_raw(
                    200, engine.obs.registry.render_prometheus().encode(),
                    "text/plain; version=0.0.4; charset=utf-8")
            elif self.path == "/trace":
                self._reply(200, engine.obs.tracer.to_chrome())
            else:
                self._reply(404, {"error": "unknown path"})

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            try:
                payload = json.loads(self.rfile.read(n) or b"{}")
            except json.JSONDecodeError:
                return self._reply(400, {"error": "bad json"})
            if self.path == "/infer":
                from repro.serve import RejectedError

                toks = payload.get("tokens")
                if not isinstance(toks, list) or not toks:
                    return self._reply(400, {"error": "tokens: [word ids]"})
                deadline = payload.get("deadline_ms")
                try:
                    res = engine.infer(toks, deadline_ms=deadline)
                except RejectedError as e:
                    # admission control said no — structured 429 so clients
                    # can back off / retry against another replica
                    return self._reply(429, {
                        "error": str(e), "reason": e.reason,
                        "queue_depth": e.queue_depth,
                        "max_queue": e.max_queue})
                except (ValueError, TypeError) as e:
                    return self._reply(400, {"error": str(e)})
                except (RuntimeError, TimeoutError) as e:
                    return self._reply(500, {"error": str(e)})
                return self._reply(200, {
                    "top_topics": res["top_topics"].tolist(),
                    "top_weights": res["top_weights"].tolist(),
                    "theta": res["theta"].tolist(),
                    "model_version": res["model_version"],
                    "truncated": bool(res["truncated"]),
                    "latency_ms": res["latency_ms"],
                })
            if self.path == "/swap":
                from repro.serve import PublishError, SnapshotIntegrityError

                path = payload.get("snapshot")
                if not path or not os.path.exists(path):
                    return self._reply(400, {"error": "snapshot path missing"})
                try:
                    v = model.publish(load_model(args, path))
                except (PublishError, SnapshotIntegrityError) as e:
                    # failed publish rolled back: still serving the last
                    # good snapshot — transient server-side condition
                    return self._reply(503, {
                        "error": str(e), "rolled_back": True,
                        "model_version": model.version})
                except Exception as e:  # corrupt / non-snapshot file
                    return self._reply(400, {"error": f"bad snapshot: {e}"})
                return self._reply(200, {"model_version": v})
            return self._reply(404, {"error": "unknown path"})

    return ThreadingHTTPServer((args.host, args.port), Handler)


def run_http(args) -> int:
    fault_plan = make_fault_plan(args)
    snap = load_model(args, fault_plan=fault_plan)
    model, engine = make_engine(args, snap, fault_plan=fault_plan)
    httpd = make_http_server(args, model, engine)
    print(f"[serve] V={snap.num_words} K={snap.num_topics} on "
          f"http://{args.host}:{httpd.server_address[1]}")
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        _dump_obs(args, model, engine)
        engine.stop()
        httpd.server_close()
    return 0


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    return run_bench(args) if args.bench else run_http(args)


if __name__ == "__main__":
    sys.exit(main())
