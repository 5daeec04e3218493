"""Trace regions inside the sampler kernel, reduced from a profiler trace.

Mosaic lowers a ``jax.named_scope`` inside a Pallas kernel body to a device
trace region; ``lda_sample`` has three (``REGIONS``), each one event per
grid step (one word tile).  The TPU records them only for programs compiled
while libtpu runs with ``REGION_FLAG`` in ``LIBTPU_INIT_ARGS``, and writes
them on the ``XLA TraceMe`` line of each device plane, apart from the
``XLA Ops`` line that ``bench.trace`` reduces, so they never enter
``Summary.ops``.  The flag also adds a ``Tensor Core`` line of per-bundle
events, which nothing reads.  ``LIBTPU_INIT_ARGS`` is part of the key of
JAX's persistent compile cache, so a run without the flag never loads a
program compiled under it, nor the reverse.

A region reads a time only if the window holds every one of its events:
grid steps times calls.  Otherwise the trace lost some, the counts go to
standard error and the region reads nothing.

    python3 bench/regions.py --workload <cell> --seed <n> --stride <k>

times the regions of a training cell's kernel on one chip: it builds the
cell's shard and state as ``bench/drivers/train.py`` does, runs the plan
over the whole shard and the kernel over every k-th word tile under the
profiler and the flag, and prints one JSON line: the device time of the
op, of each region and of the residual (the op's time outside the
regions), per tile and scaled to all the shard's tiles.  A whole traced
iteration does not fit a run: under the flag the per-bundle line of one
NYTimes iteration runs to gigabytes.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path

REGION_FLAG = "--xla_enable_custom_call_region_trace=true"
REGIONS_LINE = "XLA TraceMe"
KERNEL = "lda_sample"
REGIONS = ("lda_sample.issue", "lda_sample.wait", "lda_sample.rows")


def with_flag(libtpu_args: str) -> str:
    """``LIBTPU_INIT_ARGS`` with the region flag appended, never replaced."""
    if REGION_FLAG in libtpu_args.split():
        return libtpu_args
    return f"{libtpu_args} {REGION_FLAG}".strip()


def line_events(data: bytes, device_ids, line: str) -> list:
    """(start_ns, end_ns, name) of every event on the line called ``line``
    of ``device_ids``' planes.  Other lines are skipped undecoded (the
    name precedes the events on the wire): under the flag the per-bundle
    line holds most of the trace's bytes."""
    from bench import trace

    def picked(raw) -> list:
        ts, evs = 0, []
        for f, y in trace._fields(raw):
            if f == 2 and bytes(y).decode(errors="replace") != line:
                return []
            if f == 3:
                ts = y
            elif f == 4:
                mid = off = dur = 0
                for ef, z in trace._fields(y):
                    if ef == 1:
                        mid = z
                    elif ef == 2:
                        off = z
                    elif ef == 3:
                        dur = z
                s = ts + off // 1000
                evs.append((s, s + dur // 1000, mid))
        return evs

    out = []
    for f, plane in trace._fields(memoryview(data)):
        if f != 1:
            continue
        pname, names, evs = "", {}, []
        for pf, x in trace._fields(plane):
            if pf == 2:
                pname = bytes(x).decode(errors="replace")
            elif pf == 3:
                evs += picked(x)
            elif pf == 4:
                k, v = trace._map_entry(x)
                for mf, y in trace._fields(v or b""):
                    if mf == 2:
                        names[k] = bytes(y).decode(errors="replace")
        if trace._device_index(pname) in device_ids:
            out += [(s, e, names.get(mid, "?")) for s, e, mid in evs]
    return out


def events(data: bytes, device_ids, start: int, end: int) -> dict:
    """Region name -> [(start_ns, end_ns)] of the events of ``device_ids``'
    ``XLA TraceMe`` lines that overlap the window, clipped to it."""
    out: dict[str, list] = {}
    for s, e, name in line_events(data, device_ids, REGIONS_LINE):
        if e > start and s < end:
            out.setdefault(name, []).append((max(s, start), min(e, end)))
    return out


def grid_steps(hlo_texts, kernel: str = KERNEL):
    """The grid steps of ``kernel``'s custom call in the compiled HLO: the
    leading dimension of its first output, one word tile per step."""
    pat = re.compile(r"%" + re.escape(kernel) + r"(?:\.\d+)? = \(?\w+\[(\d+)")
    for text in hlo_texts:
        m = pat.search(text)
        if m:
            return int(m.group(1))
    return None


def per_call_ms(regions: dict, name: str, calls: int, steps):
    """Device milliseconds per kernel call in region ``name``, or None when
    the window does not hold exactly ``steps`` events per call."""
    evs = regions.get(name, [])
    if not calls or not steps or len(evs) != calls * steps:
        print(f"regions: {name} has {len(evs)} events in the window, not "
              f"{steps} grid steps x {calls} calls; not read",
              file=sys.stderr, flush=True)
        return None
    return sum(e - s for s, e in evs) / calls / 1e6


def split(summary, regions: dict, steps) -> dict:
    """The kernel op's device time per call, that of each region, and the
    residual outside them (``None`` where a region is incomplete)."""
    op_ns, calls = 0, 0
    for op in summary.ops:
        if op.name == KERNEL or op.name.startswith(KERNEL + "."):
            s, e = max(op.start, summary.start), min(op.end, summary.end)
            if e > s:
                op_ns, calls = op_ns + e - s, calls + 1
    out = {"calls": calls, "grid_steps": steps,
           "events": {r: len(regions.get(r, [])) for r in REGIONS},
           "op_ms": op_ns / calls / 1e6 if calls else None}
    ms = {r: per_call_ms(regions, r, calls, steps) for r in REGIONS}
    out["region_ms"] = ms
    done = out["op_ms"] is not None and None not in ms.values()
    out["residual_ms"] = out["op_ms"] - sum(ms.values()) if done else None
    return out


def sample_tiles(workload: str, seed: int, stride: int) -> dict:
    """Every ``stride``-th word tile of a training cell's shard, sampled
    once under the profiler (after a warm-up call), with the plan's ELL
    of the whole shard: the kernel's time and its regions per tile, and
    the same scaled to all the shard's tiles."""
    import functools
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import generator, spec, trace
    from bench.drivers import train as driver
    from repro.core import corpus as pcorpus
    from repro.core import trainer
    from repro.kernels import resolve_interpret
    from repro.kernels.lda_sample import ops

    root = Path(__file__).resolve().parents[1]
    cfg = spec.resolve(root, workload).config
    D, V = int(cfg["num_docs"]), int(cfg["num_words"])
    d, w, z = generator.training_corpus(cfg, seed)
    corpus = pcorpus.Corpus(d, w, D, V)
    lcfg = trainer.resolve_config(driver.lda_config(cfg), corpus)
    shard = pcorpus.tile_shard(corpus, np.arange(D, dtype=np.int32),
                               lcfg.tile_tokens)
    uid = np.asarray(shard.token_uid)
    z_tiled = np.where(uid >= 0, z[np.maximum(uid, 0)], 0)
    state = jax.jit(functools.partial(trainer.state_from_z, lcfg))(
        shard, jnp.asarray(z_tiled.astype(lcfg.topic_dtype)), 0)
    n = int(shard.tile_word.shape[0])
    idx = jnp.arange(0, n, stride)

    @jax.jit
    def sweep(shard, state, key, idx):
        with jax.named_scope("lda.plan"):
            _, ell_c, ell_t, _ = trainer._build_theta_ell(
                lcfg, shard, state.z, None)
        with jax.named_scope("lda.sample"):
            z_new, _ = ops.lda_sample(
                shard.tile_word[idx], shard.token_doc[idx],
                shard.token_mask[idx], state.z[idx], state.phi_vk,
                state.phi_sum, ell_c, ell_t, key,
                alpha=lcfg.resolved_alpha(), beta=lcfg.beta,
                num_words_total=V, impl="pallas",
                interpret=resolve_interpret())
        return z_new

    key = driver.key_for(seed)
    sweep(shard, state, key, idx).block_until_ready()
    dev = jax.devices()[0]
    with tempfile.TemporaryDirectory() as tdir:
        jax.profiler.start_trace(tdir, profiler_options=trace.profile_options())
        sweep(shard, state, key, idx).block_until_ready()
        jax.profiler.stop_trace()
        data = next(Path(tdir).rglob("*.xplane.pb")).read_bytes()
    kernel_ops = [trace.Op(s, e, trace.short_name(name), "", 0)
                  for s, e, name in line_events(data, [dev.id], trace.OPS_LINE)
                  if trace.short_name(name).startswith(KERNEL)]
    start = min((o.start for o in kernel_ops), default=0)
    end = max((o.end for o in kernel_ops), default=0)
    summary = trace.Summary(kernel_ops, [], start, end, 1)
    out = split(summary, events(data, [dev.id], start, end), len(idx))
    real = np.asarray(shard.token_mask).sum(axis=1)
    out.update(workload=workload, tiles=n, stride=stride,
               tokens_per_tile=float(real.mean()),
               sampled_tokens_per_tile=float(real[np.asarray(idx)].mean()))
    times = dict(out["region_ms"], op=out["op_ms"], residual=out["residual_ms"])
    out["per_tile_us"] = {k: None if v is None else v * 1e3 / len(idx)
                          for k, v in times.items()}
    out["all_tiles_s"] = {k: None if v is None else v * n / 1e6
                          for k, v in out["per_tile_us"].items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--stride", type=int, required=True)
    args = ap.parse_args(argv)
    os.environ["LIBTPU_INIT_ARGS"] = with_flag(
        os.environ.get("LIBTPU_INIT_ARGS", ""))
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    print(json.dumps(sample_tiles(args.workload, args.seed, args.stride)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
