"""kernel-contract metadata for the serving fold-in kernel.

One grid step per request doc; the doc's (L, K) p* rows (double
buffered) and their block prefix sums are the VMEM heavyweights — about
10 MiB in the paper-scale case (L=512, K=1024) under a 16 MiB budget.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.analysis.contracts import ContractCase, KernelContract, Operand
from repro.kernels.fold_in import kernel

VMEM_BUDGET_BYTES = 16 * 1024 * 1024


def _doc_slice_nB(batch: int, shards: int) -> int:
    """Per-shard slice width the sharded serving path launches with —
    derived from the same ``doc_slice_bounds`` the a2a fold-in slices by,
    so the contract covers the sharded (ceil-divided, overlapping) launch
    geometry, not just host-chosen batch sizes."""
    from repro.distributed.partition import doc_slice_bounds
    _, per = doc_slice_bounds(batch, shards)
    return per


def _case(name: str, *, nB: int, L: int, K: int, n_sweeps: int
          ) -> ContractCase:
    P = min(L, K)
    grid, in_specs, out_specs, scratch = kernel.grid_layout(
        nB, L, K, n_sweeps, P)
    inputs = (
        Operand("pstar_tok", (nB, L, K), jnp.float32, in_specs[0]),
        Operand("alpha", (1, 1), jnp.float32, in_specs[1]),
        Operand("u1", (nB, n_sweeps, 1, L), jnp.float32, in_specs[2]),
        Operand("u2", (nB, n_sweeps, 1, L), jnp.float32, in_specs[3]),
        Operand("mask", (nB, 1, L), jnp.int32, in_specs[4]),
        Operand("z0", (nB, 1, L), jnp.int32, in_specs[5]),
    )
    outputs = (
        Operand("theta_sum", (nB, 1, K), jnp.int32, out_specs[0]),
        Operand("sp", (nB, 1, 1), jnp.int32, out_specs[1]),
        Operand("ssq", (nB, 1, 1), jnp.float32, out_specs[2]),
    )
    return ContractCase(
        name=name, grid=grid, inputs=inputs, outputs=outputs,
        scratch=tuple(scratch), coverage=("theta_sum", "sp", "ssq"))


def contract() -> KernelContract:
    return KernelContract(
        kernel="fold_in",
        vmem_budget_bytes=VMEM_BUDGET_BYTES,
        cases=(
            _case("tiny", nB=4, L=8, K=16, n_sweeps=3),
            # paper-representative: a NYTimes-length bucket at NYTimes K
            # with the default 8+4 sweep schedule
            _case("paper", nB=32, L=512, K=1024, n_sweeps=12),
            # sharded doc slice: B=10 over S=4 shards -> per-shard nB=3
            # (ceil division, trailing slices overlap), odd L
            _case("doc-slice", nB=_doc_slice_nB(10, 4), L=17, K=24,
                  n_sweeps=5),
        ))
