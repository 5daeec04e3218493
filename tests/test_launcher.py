"""Launcher-level integration: the production entry point trains, checkpoints,
and resumes after a simulated failure (fresh process = killed job restart)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_train(tmp, iters, extra=()):
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    cmd = [sys.executable, "-m", "repro.launch.train",
           "--iters", str(iters), "--topics", "16", "--scale", "0.0001",
           "--ckpt-dir", os.path.join(tmp, "ck"), "--ckpt-every", "5",
           *extra]
    res = subprocess.run(cmd, capture_output=True, text=True, env=env,
                         timeout=420)
    assert res.returncode == 0, res.stderr[-2000:]
    return res.stdout


@pytest.mark.slow
def test_train_checkpoint_and_resume(tmp_path):
    tmp = str(tmp_path)
    run_train(tmp, 10)
    # "job restart": a fresh process must resume from iteration 10, not 0
    out = run_train(tmp, 20)
    assert "[resume] iteration 10" in out, out


@pytest.mark.slow
def test_train_elastic_resume_2d(tmp_path):
    """Resume the same checkpoint on a different partition mode (elastic)."""
    tmp = str(tmp_path)
    run_train(tmp, 10)
    out = run_train(tmp, 15, extra=("--mode", "2d"))
    assert "[resume] iteration 10" in out, out
