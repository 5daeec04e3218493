"""Serving fold-in kernel package (kernel.py / ref.py / ops.py).

First *inference* kernel in the repo: the frozen-phi fold-in sweep of
``repro.serve.infer`` with the whole sweep loop fused on-chip.  Same layout
contract as ``repro.kernels.lda_sample`` — a Pallas kernel, a pure-jnp
oracle it must match bit-for-bit (also the ``"xla"`` serving path), and a
public wrapper with an ``impl={"xla","pallas"}`` switch.
"""
from repro.kernels.fold_in.ops import fold_in_sweeps

__all__ = ["fold_in_sweeps"]
