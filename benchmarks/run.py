"""Benchmark entry point — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  A section that raises
prints an ``<name>_ERROR`` row, the other sections still run, and the
command exits non-zero.

    PYTHONPATH=src python -m benchmarks.run [--only table4 ...]
"""
import argparse
import sys

sys.path.insert(0, "src")

from . import (breakdown, convergence, flops_byte, kernels_bench,
               roofline_tables, scaling, serving, throughput)

SECTIONS = {
    "table1": flops_byte.run,       # Flops/Byte characterization
    "table4": throughput.run,       # tokens/sec
    "fig8": convergence.run,        # LL vs iterations
    "fig9": scaling.run,            # multi-device scaling
    "table5": breakdown.run,        # time breakdown
    "kernels": kernels_bench.run,   # Pallas kernel paths
    "roofline": roofline_tables.run,
    "serving": serving.run,         # fold-in latency/throughput (repro.serve)
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    choices=sorted(SECTIONS))
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failed = []
    for name, fn in SECTIONS.items():
        if args.only and name not in args.only:
            continue
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — report, run the rest, fail
            print(f"{name}_ERROR,0,{type(e).__name__}: {e}")
            failed.append(name)
    if failed:
        print(f"failed sections: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
