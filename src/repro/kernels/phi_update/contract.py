"""kernel-contract metadata for the phi count-update kernel.

The grid walks word-sorted tiles accumulating into one VMEM row, which is
written to its word's HBM row after the word's last tile.  The checks here
assert the word-boundary discipline on the kernel's own ``tile_meta``:
tiles are word-sorted, exactly one first flag opens and exactly one last
flag closes each contiguous word run (so the zero-init and the single
write produce exact counts), and every word's row is written.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.analysis.contracts import ContractCase, KernelContract, Operand
from repro.kernels.phi_update import kernel

VMEM_BUDGET_BYTES = 128 * 1024


def _word_sorted_tiles(n: int, V: int) -> tuple[np.ndarray, np.ndarray]:
    """(tile_word, tile_first) with word-sorted tiles covering every word
    (the trainer's host-side layout)."""
    tile_word = np.sort((np.arange(n, dtype=np.int32) * V) // n)
    tile_first = np.r_[1, (np.diff(tile_word) != 0).astype(np.int32)]
    return tile_word, tile_first.astype(np.int32)


def _case(name: str, *, n: int, t: int, V: int, K: int, delta: bool
          ) -> ContractCase:
    tile_word, tile_first = _word_sorted_tiles(n, V)
    meta = np.asarray(kernel.tile_meta(tile_word, tile_first))[:, 0]
    grid, in_specs, out_spec, scratch = kernel.grid_layout(n, t, K,
                                                           delta=delta)
    names = ("z_new", "z_old", "mask") if delta else ("z", "mask")
    inputs = (Operand("meta", (n, 1, kernel.META), jnp.int32, in_specs[0]),)
    inputs += tuple(Operand(nm, (n, 1, t), jnp.int32, spec)
                    for nm, spec in zip(names, in_specs[1:]))
    outputs = (Operand("phi_delta", (V, 1, K), jnp.int32, out_spec),)

    def word_run_invariant():
        msgs = []
        w, first, last = meta[:, 0], meta[:, 1], meta[:, 2]
        if not np.array_equal(w, np.sort(w)):
            msgs.append("tile_word not word-sorted — a word's row would be "
                        "written before all its tiles accumulated")
        expect_first = np.r_[1, (np.diff(w) != 0).astype(np.int32)]
        if not np.array_equal(first, expect_first):
            msgs.append("tile_first != first-tile-of-each-word-run — the "
                        "zero-init would drop or double counts")
        expect_last = np.r_[(np.diff(w) != 0).astype(np.int32), 1]
        if not np.array_equal(last, expect_last):
            msgs.append("last-tile flags != end of each word run — a row "
                        "would be written early, twice or never")
        missing = np.setdiff1d(np.arange(V), w[last == 1])
        if missing.size:
            msgs.append(f"{missing.size} phi rows never written "
                        f"(e.g. word {int(missing[0])})")
        return msgs

    return ContractCase(
        name=name, grid=grid, inputs=inputs, outputs=outputs,
        scratch=tuple(scratch), extra_checks=(word_run_invariant,))


def contract() -> KernelContract:
    return KernelContract(
        kernel="phi_update",
        vmem_budget_bytes=VMEM_BUDGET_BYTES,
        cases=(
            _case("tiny-rebuild", n=10, t=8, V=6, K=16, delta=False),
            _case("tiny-delta", n=10, t=8, V=6, K=16, delta=True),
            # paper-representative tile count at NYTimes K
            _case("paper-delta", n=1024, t=256, V=512, K=1024, delta=True),
        ))
