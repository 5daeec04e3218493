"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix
and per-layer metrics are files under ``bench/`` found by name (see
``bench/spec.py``).  Set-up (generation, tiling, transfer, compilation or
compile-cache load, warm-up) is timed from the process's start as
``setup_s``; then the window runs for ``--seconds``; then the program's
state is brought to the host, the device's peak memory read, and the
window's output compared with ``bench/reference.py``.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and the result
carries the per-layer metrics, the device's busy and window seconds and a
breakdown of device time and idle gaps.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result; it never falls back to the CPU.  The last
lines of standard error, and the result's last key, give each number
compared beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell's data, the run's arguments, limits."""

    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    limits: dict
    chips: int

    def span(self, name: str):
        """A host span on the profiler's clock (free when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @staticmethod
    def log(msg: str) -> None:
        print(f"[bench {time.perf_counter() - T_START:8.2f}s] {msg}",
              file=sys.stderr, flush=True)


def use_compile_cache(root: Path) -> str:
    """JAX's persistent cache at a fixed directory of the checkout (or the
    one ``JAX_COMPILATION_CACHE_DIR`` names), caching every program."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {len(devs)} {devs[0].platform} "
                       f"device(s); the benchmark never falls back to the "
                       f"CPU")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def judge(numbers: list, limits: dict) -> tuple[bool, dict]:
    """Each number compared beside its limit, and whether all hold."""
    shown, ok = {}, True
    for name, value in numbers:
        if name not in limits:
            raise KeyError(f"no limit for {name!r}")
        limit = limits[name]
        shown[name] = {"value": value, "limit": limit}
        ok = ok and value <= limit
    return ok, shown


def per_layer(cell, reading) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m["name"])(reading)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reads: the reduced trace, the window's
    counters and the work counted from the data."""

    trace: object
    window: dict
    work: dict
    peaks: dict


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object."""
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import spec
    from bench import trace as tracing

    cell = spec.resolve(root, workload)
    limits = spec.load_json(root / "bench" / "limits" / f"{workload}.json")
    use_compile_cache(root)
    devs = devices(cell.chips, require_tpu)
    dev = devs[0]
    ctx = Context(cell.config, cell.traffic, seed, seconds, trace,
                  limits["limits"], cell.chips)
    ctx.log(f"{workload}: {len(devs)} x {dev.device_kind} ({dev.platform})")
    drv = cell.driver
    st = drv.setup(ctx)
    setup_s = time.perf_counter() - T_START
    ctx.log(f"set-up {setup_s:.2f} s")

    with contextlib.ExitStack() as stack:
        tdir = None
        if trace:
            import jax
            tdir = stack.enter_context(tempfile.TemporaryDirectory())
            jax.profiler.start_trace(
                tdir, profiler_options=tracing.profile_options())
        with ctx.span("bench.window"):
            w = drv.window(st, ctx, seconds)
        if trace:
            jax.profiler.stop_trace()
            summary = tracing.reduce(tdir, devs, drv.hlo_texts(st))
        mem = memory_peak(devs)
    ctx.log(f"window {w['window_s']:.2f} s: "
            + ", ".join(f"{k} {v}" for k, v in w["metrics"].items()))
    drv.release(st)
    metrics: dict
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if trace:
        reading = Reading(summary, w, drv.work_counts(st),
                          spec.peaks(dev.device_kind, root / "bench"))
        metrics = per_layer(cell, reading)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = summary.breakdown()
    else:
        values = dict(w["metrics"], setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    numbers = drv.check(st, ctx)
    correct, shown = judge(numbers, ctx.limits)
    result = {"correct": correct, "attempted": w["attempted"],
              "failed": w["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = shown
    for name, v in shown.items():
        print(f"check {name} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    except ImportError as e:
        print(f"bench: cannot import the system under test: {e}",
              file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
