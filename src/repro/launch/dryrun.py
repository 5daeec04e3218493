import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces:
  * proof of compilation on the production meshes (16x16 single-pod and
    2x16x16 multi-pod);
  * per-device memory from ``compiled.memory_analysis()`` (must fit 16 GiB);
  * roofline raw numbers: HLO FLOPs / bytes via the 1-block/2-block probe
    extrapolation (scan bodies are counted once by cost_analysis — verified
    in-container), collective bytes parsed from the probe HLO text;
  * the LDA cells (the paper's own workload) on the same meshes.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs.archs import ARCHS, SHAPES, cells, skipped_cells
from repro.launch import mesh as mesh_lib
from repro.launch import specs as specs_lib

_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s16": 2,
                "u16": 2, "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8,
                "u64": 8, "c64": 8}

_COLL_OP_RE = re.compile(
    r"=\s+(\(?[^=()]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in the HLO text.

    Handles XLA's all-reduce **combiner**, which merges several reductions
    into one op with a tuple result: ``(s32[...], s32[...]) all-reduce(...)``.
    ``-done`` ops are skipped (their ``-start`` pair carries the shape).
    """
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_OP_RE.search(line)
        if not m or "-done(" in line:
            continue
        result, op = m.group(1), m.group(2)
        total = 0
        for dtype, dims in _SHAPE_RE.findall(result):
            if dtype not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dtype]
        out[op] = out.get(op, 0) + total
    return out


def _probe_once(arch: str, shape: str, mesh, nb: int, micro: int) -> dict:
    cfg = ARCHS[arch]
    small = dataclasses.replace(
        cfg, num_layers=nb * len(cfg.pattern) + len(cfg.tail))
    orig_u = specs_lib.TRAIN_MICRO.get(arch)
    try:
        if micro is not None:
            specs_lib.TRAIN_MICRO[arch] = micro
        with _patched_arch(arch, small):
            cell = specs_lib.build_cell(arch, shape, mesh)
            compiled = cell.fn.lower(*cell.args).compile()
    finally:
        if orig_u is None:
            specs_lib.TRAIN_MICRO.pop(arch, None)
        else:
            specs_lib.TRAIN_MICRO[arch] = orig_u
    ca = compiled.cost_analysis()
    return dict(flops=float(ca.get("flops", 0) or 0),
                bytes=float(ca.get("bytes accessed", 0) or 0),
                coll=collective_bytes(compiled.as_text()))


def probe_costs(arch: str, shape: str, mesh) -> dict:
    """Per-block extrapolation at micro_batches=1 + analytic re-gather term.

    FLOPs/bytes are token-linear, so gradient accumulation does not change
    the per-step totals — probing at u=1 (where nothing is scanned over
    microbatches) gives them exactly:
        total = c(1blk) + (NB-1) * (c(2blk) - c(1blk)).
    Collectives are NOT token-linear: every microbatch re-gathers the FSDP
    weight shards.  That term is added analytically:
        regather = (U-1) * sum(param_bytes_bf16) * (dp-1)/dp   per device.
    """
    nb_full = ARCHS[arch].num_blocks
    u_full = (specs_lib.TRAIN_MICRO.get(arch, 1)
              if SHAPES[shape]["kind"] == "train" else 1)
    c11 = _probe_once(arch, shape, mesh, 1, 1)
    c21 = _probe_once(arch, shape, mesh, 2, 1)

    def extrap(a, b):
        return a + (nb_full - 1) * max(b - a, 0.0)

    coll = {}
    for k in set(c11["coll"]) | set(c21["coll"]):
        coll[k] = int(extrap(c11["coll"].get(k, 0), c21["coll"].get(k, 0)))
    if u_full > 1:
        from repro.launch.roofline import param_counts
        total_params, _ = param_counts(ARCHS[arch])
        dp = 16  # data-axis size of the single-pod mesh
        regather = int((u_full - 1) * total_params * 2 * (dp - 1) / dp)
        coll["all-gather"] = coll.get("all-gather", 0) + regather
    return dict(
        hlo_flops=extrap(c11["flops"], c21["flops"]),
        hlo_bytes=extrap(c11["bytes"], c21["bytes"]),
        coll_bytes=coll,
        probe=dict(num_blocks=nb_full, micro=u_full, one=c11, two=c21),
    )


class _patched_arch:
    """Temporarily swap an arch's config (probe compiles)."""

    def __init__(self, name: str, cfg):
        self.name, self.cfg = name, cfg

    def __enter__(self):
        self.orig = ARCHS[self.name]
        ARCHS[self.name] = self.cfg

    def __exit__(self, *a):
        ARCHS[self.name] = self.orig


def run_cell(arch: str, shape: str, multi_pod: bool, probe: bool = True) -> dict:
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    cell = specs_lib.build_cell(arch, shape, mesh)
    lowered = cell.fn.lower(*cell.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    mem = dict(
        argument_bytes=ma.argument_size_in_bytes,
        output_bytes=ma.output_size_in_bytes,
        temp_bytes=ma.temp_size_in_bytes,
        alias_bytes=ma.alias_size_in_bytes,
        peak_device_bytes=(ma.argument_size_in_bytes + ma.output_size_in_bytes
                           + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
    )
    ca = compiled.cost_analysis()
    out = dict(
        arch=arch, shape=shape,
        mesh="2x16x16" if multi_pod else "16x16",
        status="ok", t_lower=round(t_lower, 1), t_compile=round(t_compile, 1),
        memory=mem,
        scan_cost=dict(flops=float(ca.get("flops", 0) or 0),
                       bytes=float(ca.get("bytes accessed", 0) or 0)),
        fits_hbm=bool(mem["peak_device_bytes"] <= mesh_lib.device_peaks(
            mesh_lib.PRODUCTION_KIND)["hbm_bytes"]),
    )
    if probe and not multi_pod:
        out["costs"] = probe_costs(arch, shape, mesh)
    return out


def run_lda_cell(multi_pod: bool, num_topics: int = 1024,
                 dataset: str = "nytimes") -> dict:
    """The paper's own workload on the production mesh: both partition modes.

    Corpus stand-in is shape-accurate (NYTimes/PubMed Table 3 statistics,
    scaled so host tiling is fast); phi/collective volumes use the real K*V."""
    from repro.core import trainer as lda_trainer
    from repro.data import synthetic
    from repro.distributed.partition import DistributedLDA

    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    full = dict(nytimes=(101_636, 332), pubmed=(141_043, 92))[dataset]
    V, avg_len = full
    n_dev = int(np.prod(mesh.devices.shape))
    # stand-in corpus: ~2k tokens/device keeps host tiling tractable; the
    # model-side arrays (phi K x V) are FULL SIZE — they dominate the roofline
    corpus = synthetic.zipf_corpus(num_docs=max(n_dev * 8, 4096),
                                   num_words=V, avg_doc_len=avg_len, seed=0)
    results = {}
    for mode, comp in (("1d", False), ("2d", False), ("1d_c16", True),
                       ("2d_c16", True)):
        base = mode.split("_")[0]
        doc_axes = (tuple(mesh.axis_names) if base == "1d"
                    else tuple(a for a in mesh.axis_names if a != "model"))
        cfg = lda_trainer.LDAConfig(num_topics=num_topics, tile_tokens=256,
                                    tiles_per_step=16, compressed_sync=comp)
        dl = DistributedLDA(cfg, mesh, corpus, mode=base, doc_axes=doc_axes,
                            word_axes=("model",) if base == "2d" else ())
        t0 = time.time()
        lowered = dl.lower_step()
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        results[mode] = dict(
            t_compile=round(time.time() - t0, 1),
            peak_device_bytes=(ma.argument_size_in_bytes
                               + ma.output_size_in_bytes
                               + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
            flops=float(ca.get("flops", 0) or 0),
            bytes=float(ca.get("bytes accessed", 0) or 0),
            coll_bytes=collective_bytes(compiled.as_text()),
        )
    return dict(arch=f"lda-{dataset}-k{num_topics}",
                mesh="2x16x16" if multi_pod else "16x16",
                status="ok", modes=results)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--lda", action="store_true")
    ap.add_argument("--no-probe", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    todo = (cells() if args.all else [(args.arch, args.shape)])
    results = []
    for mp in meshes:
        if args.lda:
            for ds in ("nytimes", "pubmed"):
                try:
                    r = run_lda_cell(mp, dataset=ds)
                except Exception as e:  # noqa: BLE001
                    r = dict(arch=f"lda-{ds}", mesh=str(mp), status="fail",
                             error=f"{type(e).__name__}: {e}")
                print(json.dumps(r), flush=True)
                results.append(r)
            continue
        for arch, shape in todo:
            jax.clear_caches()  # keep the long sweep's memory bounded
            try:
                r = run_cell(arch, shape, mp, probe=not args.no_probe)
            except Exception as e:  # noqa: BLE001
                r = dict(arch=arch, shape=shape, mesh=str(mp), status="fail",
                         error=f"{type(e).__name__}: {e}",
                         tb=traceback.format_exc()[-2000:])
            print(json.dumps(r), flush=True)
            results.append(r)

    for a, sh, why in skipped_cells():
        results.append(dict(arch=a, shape=sh, status="skip", reason=why))

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
    bad = [r for r in results if r.get("status") == "fail"]
    print(f"\n{len(results)} cells, {len(bad)} failures", file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
