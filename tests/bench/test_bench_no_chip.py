"""Without a TPU the benchmark exits non-zero and prints no result."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def run(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_cpu_backend_exits_nonzero_without_a_result_line():
    r = run("--workload", "nytimes.train", "--seed", "3", "--seconds", "1",
            "--trace", "0")
    assert r.returncode != 0
    assert "{" not in r.stdout
    assert "no TPU" in r.stderr


def test_unknown_workload_exits_nonzero():
    r = run("--workload", "nope", "--seed", "3", "--seconds", "1")
    assert r.returncode != 0 and "{" not in r.stdout
