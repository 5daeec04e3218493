"""Production meshes.

Never touches jax device state at import time — meshes are built by
functions.  The TPU-v5e production target is 16x16 = 256 chips per pod
("data" x "model"), with a third leading "pod" axis for the 2-pod (512 chip)
multi-pod dry-run.
"""
from __future__ import annotations

import jax

# Published per-chip peaks (roofline denominators), keyed by the
# ``device_kind`` JAX reports.  Source: Google Cloud documentation, "TPU
# v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of ICI over
# four links.
PEAKS = {
    "TPU v5 lite": dict(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9,
                        hbm_bytes=16 * 1024 ** 3),
}
PRODUCTION_KIND = "TPU v5 lite"   # the dry-run target: v5e pods


def device_peaks(kind: str | None = None) -> dict:
    """Peaks of ``kind`` (default: the first local device's kind).  A kind
    missing from ``PEAKS`` is an error, never a default."""
    kind = jax.devices()[0].device_kind if kind is None else kind
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (sharding constraints and
    ``shard_map`` both accept them; jax's default Explicit axes do not)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n: int | None = None, name: str = "data"):
    """All (or n) local devices on one axis — CPU tests and examples."""
    devs = jax.devices()
    n = len(devs) if n is None else n
    return make_mesh((n,), (name,))
