"""Paper Fig 9: multi-device scaling (1/2/4/8 host devices, 1D partition)
plus the beyond-paper 2D partition at 4x2.  Subprocess per device count
(jax fixes the device count at init), so this runs on forced CPU host
devices only: on a TPU backend the parent already holds the chip and the
children could not reach it, so it refuses."""
import json
import os
import subprocess
import sys

from .common import emit

SCRIPT = r"""
import os, sys, time, json
sys.path.insert(0, "src")
import jax
from repro.core import trainer
from repro.data.synthetic import zipf_corpus
from repro.distributed.partition import DistributedLDA

mode = sys.argv[1]
shape = json.loads(sys.argv[2])
corpus = zipf_corpus(num_docs=256, num_words=1500, avg_doc_len=100, seed=0)
cfg = trainer.LDAConfig(num_topics=128, tile_tokens=64, tiles_per_step=16)
mesh = jax.make_mesh(tuple(shape), tuple(["data","model"][:len(shape)]))
dl = DistributedLDA(cfg, mesh, corpus, mode=mode,
                    doc_axes=("data",), word_axes=("model",) if mode=="2d" else ())
state = dl.init()
state, _ = dl.step(state)           # compile+warm
t0 = time.perf_counter()
for _ in range(5):
    state, _ = dl.step(state)
jax.block_until_ready(state.z)
dt = (time.perf_counter() - t0) / 5
print(json.dumps(dict(dt=dt, ll=dl.log_likelihood(state), T=corpus.num_tokens)))
"""


def _run(devices, mode, shape):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, "-c", SCRIPT, mode, json.dumps(shape)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def run():
    import jax
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "fig9 scaling starts one child process per device count, and "
            "this process already holds the TPU; run it with "
            "JAX_PLATFORMS=cpu (forced host devices)")
    base = None
    for g in (1, 2, 4, 8):
        r = _run(g, "1d", [g])
        if base is None:
            base = r["dt"]
        emit(f"fig9_1d_x{g}", r["dt"] * 1e6,
             f"tokens_per_sec={r['T'] / r['dt']:.3g};speedup={base / r['dt']:.2f};"
             f"ll={r['ll']:.3f};note=1phys-core-serializes-devices—"
             f"per-device-work-scales-1/{g}")
    r = _run(8, "2d", [4, 2])
    emit("fig9_2d_4x2", r["dt"] * 1e6,
         f"tokens_per_sec={r['T'] / r['dt']:.3g};speedup={base / r['dt']:.2f};"
         f"ll={r['ll']:.3f}")
