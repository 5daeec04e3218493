"""kernel-contract: executed checks over Pallas launch geometry.

Each kernel package ships a ``contract.py`` (built on
``analysis.contracts``) whose cases re-derive grid/BlockSpecs/scratch from
the SAME ``grid_layout()`` the production ``pallas_call`` launches from.
For every case this checker verifies:

- **KC001** — VMEM footprint: declared VMEM operand blocks (double
  buffered) + VMEM scratch, each padded to the TPU's (8, 128) tiles,
  within the kernel's byte budget.  SMEM blocks, HBM (``pl.ANY``)
  operands and semaphores take no VMEM.
- **KC002** — index-map bounds: every BlockSpec index map, evaluated at
  every grid point (with the case's real scalar-prefetch operands),
  yields block coordinates whose block lies fully inside the operand
  (a squeezed ``None`` block dim has size 1; HBM operands have no map).
- **KC003** — grid coverage: for outputs named in ``case.coverage``, the
  set of visited blocks equals the full tiling of the array (no tile of
  the result is left unwritten).
- **KC004** — kernel-specific invariants via ``case.extra_checks``
  (chunk-plan round trip, phi_update first-visit zeroing, ...).
"""
from __future__ import annotations

import importlib
import itertools
from pathlib import Path

import numpy as np

from .report import Finding

CHECKER = "kernel-contract"
CONTRACT_MODULES = (
    "repro.kernels.lda_sample.contract",
    "repro.kernels.fold_in.contract",
    "repro.kernels.phi_update.contract",
)


def _dims(block_shape) -> tuple[int, ...]:
    return tuple(1 if d is None else int(d) for d in block_shape)


def _vmem_bytes(shape, dtype) -> int:
    """Bytes of a VMEM buffer: the last two dims pad to (8, 128) tiles."""
    dims = list(_dims(shape)) or [1]
    dims[-1] = -(-dims[-1] // 128) * 128
    if len(dims) > 1:
        dims[-2] = -(-dims[-2] // 8) * 8
    n = 1
    for d in dims:
        n *= d
    return n * np.dtype(dtype).itemsize


def _in_vmem(spec) -> bool:
    return (spec.block_shape is not None
            and str(getattr(spec, "memory_space", None)) not in ("smem",
                                                                 "any"))


def _eval_index_map(spec, coords, scalar_args):
    idx = spec.index_map(*coords, *scalar_args)
    if not isinstance(idx, tuple):
        idx = (idx,)
    return tuple(int(v) for v in idx)


def check_contract(contract, relpath: str) -> list[Finding]:
    findings: list[Finding] = []

    def emit(code, scope, message, line=1):
        findings.append(Finding(checker=CHECKER, code=code, path=relpath,
                                line=line, scope=scope, message=message))

    for case in contract.cases:
        scope = f"{contract.kernel}:{case.name}"
        operands = list(case.inputs) + list(case.outputs)

        # KC001 — declared VMEM footprint vs budget
        vmem = sum(2 * _vmem_bytes(op.spec.block_shape, op.dtype)
                   for op in operands if _in_vmem(op.spec))
        vmem += sum(_vmem_bytes(s.shape, s.dtype) for s in case.scratch
                    if str(s.dtype) != "dma_sem")
        if vmem > contract.vmem_budget_bytes:
            emit("KC001", scope,
                 f"declared VMEM footprint {vmem} B exceeds the "
                 f"{contract.vmem_budget_bytes} B budget for "
                 f"{contract.kernel} (blocks+scratch)")

        # KC002 — index maps in bounds at every grid point; collect
        # visited blocks for KC003 along the way
        visited: dict[str, set] = {label: set() for label in case.coverage}
        reported: set[str] = set()
        for coords in itertools.product(*(range(g) for g in case.grid)):
            for op in operands:
                if op.label in reported or op.spec.block_shape is None:
                    continue
                idx = _eval_index_map(op.spec, coords, case.scalar_args)
                bs = _dims(op.spec.block_shape)
                bad = None
                if len(idx) != len(bs) or len(bs) != len(op.shape):
                    bad = (f"index map arity {len(idx)} vs block rank "
                           f"{len(bs)} vs array rank {len(op.shape)}")
                else:
                    for d, (i, b, s) in enumerate(zip(idx, bs, op.shape)):
                        if i < 0 or (i + 1) * b > s:
                            bad = (f"dim {d}: block {i} of size {b} "
                                   f"overruns extent {s}")
                            break
                if bad is not None:
                    reported.add(op.label)
                    emit("KC002", scope,
                         f"operand '{op.label}' index map out of bounds at "
                         f"grid point {coords}: {bad}")
                elif op.label in visited:
                    visited[op.label].add(idx)

        # KC003 — full tiling coverage for the named outputs
        for op in operands:
            if op.label not in case.coverage or op.label in reported:
                continue
            bs = _dims(op.spec.block_shape)
            required = set(itertools.product(
                *(range(s // b) for s, b in zip(op.shape, bs))))
            missing = required - visited[op.label]
            if missing:
                emit("KC003", scope,
                     f"output '{op.label}' tiling not covered by the grid: "
                     f"{len(missing)}/{len(required)} blocks never visited "
                     f"(e.g. {sorted(missing)[0]})")

        # KC004 — kernel-specific invariants
        for chk in case.extra_checks:
            for msg in chk():
                emit("KC004", scope, f"{msg}")

    return findings


def run(root: Path) -> list[Finding]:
    findings = []
    for name in CONTRACT_MODULES:
        mod = importlib.import_module(name)
        rel = Path(mod.__file__).resolve().relative_to(
            Path(root).resolve()).as_posix()
        findings += check_contract(mod.contract(), rel)
    return findings
