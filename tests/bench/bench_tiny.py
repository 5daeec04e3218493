"""Shared set-up of the benchmark's tests, imported by each of them first:
the repository root on the import path (``bench`` is a package at the
root) and the ``tiny`` fixture, a copy of the benchmark with a
configuration small enough for the CPU.  (Not a ``conftest.py``: the
suite's own ``tests/conftest.py`` is imported by name.)"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

TINY = {
    "name": "tiny", "source": "test", "published": {
        "num_docs": 80, "num_words": 2000, "num_tokens": 3200},
    "num_docs": 80, "num_words": 2000, "num_topics": 128,
    "alpha": 0.390625, "beta": 0.01, "tile_tokens": 32,
    "precision": "float32", "reduced": [],
    "assumed": {"length_mean": 40, "length_sigma": 0.75, "length_seed": 0,
                "corpus_seed": 0,
                "doc_topic_prior": 0.1, "zipf_exponent": 1.1,
                "global_share": 0.5},
    "train": {"sampler": "pallas"},
    "serve": {"max_batch": 2, "max_delay_ms": 3.0, "length_buckets": [64],
              "admission": "block",
              "infer": {"impl": "pallas", "burn_in": 8, "samples": 4,
                        "top_k": 8}},
}


def tiny_root(dest: Path) -> Path:
    """A checkout holding the benchmark with two tiny cells:
    ``tiny.train`` and ``tiny.serve`` (40 documents a second)."""
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(ROOT / "src", dest / "src")
    (dest / "bench" / "configs" / "tiny.json").write_text(json.dumps(TINY))
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          "serve.steady.json").read_text())
    traffic["rate_docs_per_s"] = 40
    (dest / "bench" / "traffic" / "tiny.serve.json").write_text(
        json.dumps(traffic))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "CPU tests"})
    spec["workloads"] += [
        {"name": "tiny.train", "config": "tiny", "traffic": "train",
         "chips": 1, "why": "CPU tests"},
        {"name": "tiny.serve", "config": "tiny", "traffic": "tiny.serve",
         "chips": 1, "why": "CPU tests"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "nytimes.train" in m.get("workloads", ()):
            m["workloads"].append("tiny.train")
    spec["end_to_end"] += [
        {"name": "serve_p95_ms", "unit": "ms", "better": "lower",
         "bound": 0.25, "source": "host_clock", "workloads": ["tiny.serve"]},
        {"name": "serve_docs_per_s", "unit": "docs/s", "better": "higher",
         "bound": 0.01, "source": "host_clock", "workloads": ["tiny.serve"]}]
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    lim = dest / "bench" / "limits"
    shutil.copy(lim / "nytimes.train.json", lim / "tiny.train.json")
    shutil.copy(lim / "nytimes.serve.steady.json", lim / "tiny.serve.json")
    return dest


@pytest.fixture
def tiny(tmp_path) -> Path:
    return tiny_root(tmp_path)
