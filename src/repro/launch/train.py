"""Production training launcher for the paper's system.

CGS-LDA on the 1D (paper) or 2D (beyond-paper) partition with per-iteration
phi sync, checkpointing every N iterations, automatic resume, and elastic
restore onto whatever mesh this process was launched with.

On a real pod each host runs this same script (jax.distributed.initialize
discovers peers from the TPU environment); on CPU use --host-devices N to
simulate.  Fault tolerance: any host death kills the SPMD step; the job
scheduler restarts the binary, which resumes from the newest complete
checkpoint — the state is the z assignments, which are small and
partition-independent, so restarts may change the device count.
"""
from __future__ import annotations

import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["1d", "2d"], default="1d")
    ap.add_argument("--sampler", choices=["sq", "dense", "pallas"],
                    default="sq",
                    help="training sampler backend: the paper's S/Q scan, "
                         "the O(K) dense baseline, or the fused Pallas "
                         "kernel sweep (single-host and mesh alike; "
                         "interpret mode off-TPU)")
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--topics", type=int, default=1024)
    ap.add_argument("--scale", type=float, default=0.0005)
    ap.add_argument("--uci", default=None)
    ap.add_argument("--ckpt-dir", default="ckpts")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write one JSONL metrics row per training "
                         "iteration (tokens/sec, LL, sparse_frac, ...)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export host phase spans (compile/sample/eval) as "
                         "Chrome trace JSON, viewable in Perfetto")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host devices (CPU simulation)")
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() (real pod)")
    ap.add_argument("--sanitize", action="store_true",
                    help="debug mode: jax.debug_nans + transfer-guard the "
                         "sampling hot path (implicit host syncs and NaN "
                         "phi rows fail loudly)")
    args = ap.parse_args()

    if args.host_devices and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")
        os.execv(sys.executable, [sys.executable] + sys.argv)

    import jax
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    if args.sanitize:
        from repro.analysis.runtime import enable_debug_nans
        enable_debug_nans()
    if args.distributed:
        jax.distributed.initialize()

    run_lda(args)


def run_lda(args):
    import math

    import jax
    from repro.core import trainer
    from repro.core.corpus import read_uci_bow
    from repro.data.synthetic import nytimes_like
    from repro.launch.mesh import make_mesh
    from repro.obs import Observability
    from repro.train import fit

    corpus = read_uci_bow(args.uci) if args.uci else nytimes_like(args.scale)
    n_dev = len(jax.devices())
    cfg = trainer.LDAConfig(num_topics=args.topics, sampler=args.sampler)

    # every sampler — the fused Pallas sweep included — runs on the mesh
    mesh = None
    if n_dev > 1:
        if args.mode == "1d":
            mesh = make_mesh((n_dev,), ("data",))
        else:
            md = max(1, n_dev // 2)
            mesh = make_mesh((md, n_dev // md), ("data", "model"))

    # eval cadence must hit every --ckpt-every multiple AND keep the
    # every-10-iterations progress line
    ev = math.gcd(10, max(1, args.ckpt_every))
    obs = Observability.default(trace=bool(args.trace_out))
    res = fit(corpus, cfg, args.iters, mesh,
              mode=args.mode, doc_axes=("data",),
              word_axes=("model",) if args.mode == "2d" else (),
              eval_every=ev, obs=obs, metrics_out=args.metrics_out,
              sanitize=args.sanitize, checkpoint_dir=args.ckpt_dir,
              checkpoint_every=args.ckpt_every, verbose=True)
    if args.trace_out:
        print(f"[obs] trace -> {obs.tracer.export(args.trace_out)}")
    if args.metrics_out:
        print(f"[obs] per-iteration metrics -> {args.metrics_out}")
    if res.tokens_per_sec:   # empty when resume already covered --iters
        tps = sorted(res.tokens_per_sec)[len(res.tokens_per_sec) // 2]
        print(f"[done] compile {res.compile_sec:.1f}s  "
              f"median {tps / 1e6:.3f}M tok/s")


if __name__ == "__main__":
    main()
