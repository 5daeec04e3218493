"""Bytes of the phi all-reduce of one mesh training iteration, and the
chip's interconnect peak, counted from the model's shapes alone.

Under the 1d partition (the paper's section 5.2) every chip holds a (V, K)
replica of phi and all-reduces its (V, K) count delta each iteration.  A
ring all-reduce over n chips (a reduce-scatter, then an all-gather) sends
and receives ``2 (n - 1) / n`` of the payload on each chip, whatever
schedule XLA picks, so the least time it can take is that over the chip's
interconnect bandwidth.
"""
from __future__ import annotations

ICI = {
    "TPU v5 lite": {
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "Google Cloud documentation, 'TPU v5e' system "
                  "architecture: interchip interconnect 1,600 Gbps per chip",
    },
}


def payload_bytes(num_words: int, num_topics: int, itemsize: int = 4) -> int:
    """Bytes each chip all-reduces: its (V, K) phi delta."""
    return int(num_words) * int(num_topics) * int(itemsize)


def wire_bytes_per_chip(payload: int, chips: int) -> float:
    """Bytes a ring all-reduce of ``payload`` moves through each chip."""
    return 2.0 * (chips - 1) / chips * payload


def ici_bytes_per_s(device_kind: str) -> float:
    """The chip's interconnect peak; an unknown ``device_kind`` raises."""
    if device_kind not in ICI:
        raise KeyError(f"no interconnect peak for device kind "
                       f"{device_kind!r} (known: {sorted(ICI)})")
    return ICI[device_kind]["ici_bytes_per_s"]


def bound_seconds(payload: int, chips: int, device_kind: str) -> float:
    """The least time the all-reduce of ``payload`` over ``chips`` takes."""
    return wire_bytes_per_chip(payload, chips) / ici_bytes_per_s(device_kind)
