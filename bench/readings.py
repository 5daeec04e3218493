"""The readings a check's limits are set from, for one cell, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds 5]

For each seed: the cell's set-up, a short window at the cell's own load,
and the numbers its check compares (the program's readings, from which the
lower reading is the largest).  For each control seed also the control's:
``bench.reference`` in bfloat16, the precision below the configuration's,
put in the program's place (the upper reading is the smallest).  One JSON
line per seed; the benchmark's own runs never run this.  Needs the chip,
as a run does.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run, spec

    cell = spec.resolve(ROOT, args.workload)
    limits = spec.load_json(ROOT / "bench" / "limits" /
                            f"{args.workload}.json")["limits"]
    run.use_compile_cache(ROOT)
    run.devices(cell.chips, require_tpu=True)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = run.Context(cell.config, cell.traffic, seed, args.seconds,
                          False, limits, cell.chips)
        st = cell.driver.setup(ctx)
        setup = time.perf_counter() - t0
        w = cell.driver.window(st, ctx, args.seconds)
        cell.driver.release(st)
        line = {"seed": seed, "setup_s": setup, "metrics": w["metrics"],
                "program": dict(cell.driver.check(st, ctx))}
        if seed in control:
            line["control"] = dict(cell.driver.control(st, ctx))
        line["check_s"] = time.perf_counter() - t0 - setup - w["window_s"]
        print(json.dumps(line), flush=True)
        del st
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
