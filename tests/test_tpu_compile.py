"""The main path's Pallas kernels compile for a TPU v5e at NYTimes width.

Nothing runs: each test lowers and compiles one kernel for a *described*
v5e chip (no chip attached), at the paper's NYTimes shape — K=1024 topics,
V=101,636 words, 256-token tiles — so Mosaic's block-shape, lowering and
VMEM rules are checked on every change without a chip.  The sampler is
compiled at the benchmark cells' ELL widths, 1024 (NYTimes) and 896
(PubMed), with one row body per 128-lane width.  Each compile takes a few
seconds.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU compiler library, and the test
workers all import this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fold_in import kernel as fold_in_kernel
from repro.kernels.lda_sample import kernel as sample_kernel
from repro.kernels.phi_update import kernel as phi_kernel

K, V, T = 1024, 101_636, 256           # NYTimes: topics, vocab, tile
ELL_WIDTHS = (1024, 896)                 # the NYTimes and PubMed cells' P
N_TILES, D = 4096, 14_987                # a 0.05-scale NYTimes shard


@pytest.fixture(scope="module")
def topology():
    """A described v5e:2x2 host: four chips to compile for, none to run."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles can be cached but never read back
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _sweep(tw, td, pstar, cnt, tpc, u1, u2, mask, z):
    return sample_kernel.lda_sample_tiles(
        tw, td, pstar, cnt, tpc, u1, u2, mask, z, alpha=50.0 / K,
        interpret=False)


def _sweep_shapes(P):
    i32, f32 = jnp.int32, jnp.float32
    return [((N_TILES,), i32), ((N_TILES, T), i32), ((V, K), f32),
            ((D, P), i32), ((D, P), i32),
            ((N_TILES, T), f32), ((N_TILES, T), f32),
            ((N_TILES, T), i32), ((N_TILES, T), i32)]


@pytest.mark.parametrize("P", ELL_WIDTHS)
def test_lda_sample_compiles_for_v5e(one_chip, P):
    _compile(_sweep, one_chip, *_sweep_shapes(P))


def _mosaic_trace_regions(lowered_text: str) -> list[str]:
    """The ``trace_start`` messages of every Mosaic kernel body in a lowered
    program, in order (each body rides base64-encoded in its custom call)."""
    import base64
    import re

    from jaxlib.mlir import ir

    out = []
    # backend_config = "{\22custom_call_config\22: {\22body\22: \22<base64>..."
    for m in re.finditer(r"body\\22: \\22([A-Za-z0-9+/=]+)", lowered_text):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            module = ir.Module.parse(base64.b64decode(m.group(1)))

            def walk(op):
                if op.name.endswith("trace_start"):
                    out.append(ir.StringAttr(op.attributes["message"]).value)
                return ir.WalkResult.ADVANCE

            module.operation.walk(walk)
    return out


@pytest.mark.parametrize("P", ELL_WIDTHS)
def test_lda_sample_lowers_with_three_trace_regions(one_chip, P):
    """The sampler carries its three device trace regions at each cell's
    width: the DMA starts, the drain and the row loop (whatever row body
    each block takes), one per grid step."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in _sweep_shapes(P)]
    text = jax.jit(_sweep).lower(*args).as_text()
    assert _mosaic_trace_regions(text) == [
        "lda_sample.issue", "lda_sample.wait", "lda_sample.rows"]


def test_fold_in_compiles_for_v5e(one_chip):
    B, L, sweeps = 8, 512, 12           # a NYTimes-length bucket, 8+4 sweeps

    def serve(pstar_tok, alpha, u1, u2, mask, z0):
        return fold_in_kernel.fold_in_docs(
            pstar_tok, alpha, u1, u2, mask, z0, burn_in=8, samples=4,
            ell_capacity=min(L, K), interpret=False)

    i32, f32 = jnp.int32, jnp.float32
    _compile(serve, one_chip,
             ((B, L, K), f32), ((), f32),
             ((B, sweeps, L), f32), ((B, sweeps, L), f32),
             ((B, L), i32), ((B, L), i32))


@pytest.mark.parametrize("delta", [False, True])
def test_phi_update_compiles_for_v5e(one_chip, delta):
    n = 112_810                       # every tile of the 0.05-scale shard

    def update(tw, first, *rows):
        fn = phi_kernel.phi_delta_tiles if delta else phi_kernel.phi_update_tiles
        return fn(tw, first, *rows, V, K, interpret=False)

    i32 = jnp.int32
    _compile(update, one_chip, ((n,), i32), ((n,), i32),
             *[((n, T), i32)] * (3 if delta else 2))



def test_mesh_step_compiles_for_a_v5e_host(topology, monkeypatch):
    """The 1d mesh step (``DistributedLDA``, the paper's four-GPU
    partition: documents over the chips, phi replicated and all-reduced)
    compiles for a described v5e:2x2 host at NYTimes width on a small
    corpus: the sampler kernel, the phi all-reduce, and a per-chip
    footprint within one chip's 16 GB."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import trainer
    from repro.core.corpus import Corpus
    from repro.core.trainer import LDAConfig
    from repro.distributed import partition

    # described chips hold no data: keep the corpus on the host, and give
    # the compiler its shapes with the shardings the step reads them in;
    # the kernels go to Mosaic as on the chip
    monkeypatch.setattr(partition, "place_on_mesh", lambda tree, *_: tree)
    monkeypatch.setattr(trainer, "resolve_interpret", lambda *_: False)
    rng = np.random.default_rng(0)
    D, n_tok = 64, 20_000
    corpus = Corpus(np.sort(rng.integers(0, D, n_tok)).astype(np.int32),
                    rng.integers(0, V, n_tok).astype(np.int32), D, V)
    mesh = Mesh(np.array(topology.devices), ("data",))
    dl = partition.DistributedLDA(LDAConfig(num_topics=K, sampler="pallas"),
                                  mesh, corpus, mode="1d")
    lead = NamedSharding(mesh, P(("data",)))

    def shaped(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=lead)

    stacked = jax.tree.map(shaped, dl.stacked)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=NamedSharding(mesh, P()))
    state = jax.eval_shape(dl._init_fn, stacked, key)
    compiled = dl._step_fn.lower(stacked, shaped(dl._heavy), state,
                                 key).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert V * K * 4 < per_chip < 16e9
