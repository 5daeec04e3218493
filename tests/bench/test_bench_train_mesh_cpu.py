"""A whole run of the mesh training cell on four forced CPU devices at a
tiny size, with the look for a chip skipped: sound, then with planted
faults in the sync, in one shard's draws and in one chip's replica.  The
runs share one subprocess (the suite's own process keeps one device)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench_tiny import ROOT

FAULTS = ("none", "local_sync", "shard_draws", "replica")

SCRIPT = textwrap.dedent("""
    import json, shutil, sys, tempfile
    from pathlib import Path
    sys.path[:0] = [{tests!r}, {root!r}, {src!r}]
    import jax, jax.numpy as jnp
    import bench_tiny
    from bench import run
    from repro.core import sync, trainer

    root = bench_tiny.tiny_root(Path(tempfile.mkdtemp()))
    cfg = dict(bench_tiny.TINY, name="tiny.full",
               train={{"sampler": "pallas", "compressed_sync": False,
                       "sync_overlap": False}},
               deployment={{"chips": 4, "mesh_axes": ["data"],
                            "mode": "1d"}})
    (root / "bench/configs/tiny.full.json").write_text(json.dumps(cfg))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({{"name": "tiny.full", "source": "test",
                             "file": "bench/configs/tiny.full.json",
                             "reduced": [], "why": "CPU tests"}})
    spec["workloads"].append({{"name": "tiny.train.4chip",
                               "config": "tiny.full",
                               "traffic": "train.mesh", "chips": 4,
                               "why": "CPU tests"}})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "nytimes.train.4chip" in m.get("workloads", ()):
            m["workloads"].append("tiny.train.4chip")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    lim = root / "bench" / "limits"
    shutil.copy(lim / "nytimes.train.4chip.json",
                lim / "tiny.train.4chip.json")

    real_iteration, real_sync = trainer.lda_iteration, sync.sync_phi_delta

    def local_sync(delta, data_axes, heavy_rows=None, compressed=False):
        return delta                      # each chip keeps its own delta

    def shard_draws(cfg, shard, state, key, *a, **k):
        new, stats = real_iteration(cfg, shard, state, key, *a, **k)
        g = jax.lax.axis_index("data")
        bumped = ((new.z[0].astype(jnp.int32) + 1)
                  % cfg.num_topics).astype(new.z.dtype)
        return new._replace(z=new.z.at[0].set(
            jnp.where(g == 1, bumped, new.z[0]))), stats

    def replica(cfg, shard, state, key, *a, **k):
        new, stats = real_iteration(cfg, shard, state, key, *a, **k)
        g = jax.lax.axis_index("data")
        phi = new.phi_vk.at[0, 0].add(jnp.where(g == 2, 1, 0))
        return new._replace(phi_vk=phi), stats

    out = {{}}
    for fault in {faults!r}:
        sync.sync_phi_delta, trainer.lda_iteration = real_sync, real_iteration
        if fault == "local_sync":
            sync.sync_phi_delta = local_sync
        elif fault == "shard_draws":
            trainer.lda_iteration = shard_draws
        elif fault == "replica":
            trainer.lda_iteration = replica
        r = run.run_cell(root, "tiny.train.4chip", 2**33 + 29, 0.5, False,
                         require_tpu=False)
        out[fault] = r
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def results() -> dict:
    code = SCRIPT.format(tests=str(ROOT / "tests" / "bench"),
                         root=str(ROOT), src=str(ROOT / "src"),
                         faults=FAULTS)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    line = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_sound_mesh_run_is_correct(results):
    r = results["none"]
    assert r["correct"], r["checks"]
    assert r["device"]["count"] == 4
    assert set(r["checks"]) == {"layout_errors", "count_errors",
                                "replica_errors", "draw_mismatch_share"}
    assert all(v["value"] == 0 for v in r["checks"].values())
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("fault", FAULTS[1:])
def test_planted_fault_is_not_correct(results, fault):
    r = results[fault]
    assert not r["correct"], r["checks"]


def test_each_fault_shows_where_it_was_planted(results):
    checks = {f: {k: v["value"] for k, v in results[f]["checks"].items()}
              for f in FAULTS[1:]}
    assert checks["local_sync"]["replica_errors"] > 0
    assert checks["local_sync"]["count_errors"] > 0
    assert checks["shard_draws"]["count_errors"] > 0
    # chip 2's replica alone differs: phi as read (chip 0's) is sound
    assert checks["replica"]["count_errors"] == 0
    assert checks["replica"]["replica_errors"] > 0
