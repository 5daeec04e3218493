"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; the configuration is
``configs/<config>.json``, the traffic ``traffic/<traffic>.json``, whose
``driver`` names the module of ``drivers/`` that runs it, and each
per-layer metric is read by ``metrics/<metric>.py``.  Adding a cell, a
configuration or a metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


class SpecError(RuntimeError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise SpecError(f"missing {path}") from e


def load_module(path: Path, name: str):
    """Import the Python file ``path`` as a module called ``name``."""
    if not path.is_file():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with what it names, resolved."""

    name: str
    chips: int
    config: dict
    traffic: dict
    driver: object
    end_to_end: list[dict]
    per_layer: list[dict]
    bench_dir: Path

    def metric_reader(self, name: str):
        """The ``read`` function of ``metrics/<name>.py``."""
        mod = load_module(self.bench_dir / "metrics" / f"{name}.py",
                          f"bench_metric_{name.replace('.', '_')}")
        return mod.read


def _applies(metric: dict, cell: str, reported: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def resolve(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec.get("workloads", [])}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"{workload}: no configuration {w['config']!r}")
    bench_dir = root / "bench"
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    if "driver" not in traffic:
        raise SpecError(f"traffic {w['traffic']!r} names no driver")
    driver = load_module(bench_dir / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, workload, reported)]
    return Cell(workload, int(w["chips"]), config, traffic, driver, e2e,
                per_layer, bench_dir)


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    """The chip's published peaks; an unknown ``device_kind`` is an error."""
    table = load_json(bench_dir / "peaks.json")
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"peaks.json (known: {sorted(kinds)})")
    return kinds[device_kind]
