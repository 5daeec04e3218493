"""Pallas kernels of the main path: ``lda_sample`` (the training sweep),
``phi_update`` (count updates) and ``fold_in`` (serving sweeps)."""
from __future__ import annotations


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The one place Pallas interpret mode is decided.

    An explicit flag wins (tests and compile checks pass one).  Otherwise the
    kernels are compiled by Mosaic on a TPU backend and interpreted on every
    other backend; the kernel wrappers themselves take no default."""
    if interpret is not None:
        return interpret
    import jax
    return jax.default_backend() != "tpu"
