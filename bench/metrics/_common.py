"""Shared arithmetic of the metric readers (not a metric itself)."""
from __future__ import annotations


def per_unit_ms(reading, scope: str, units: float):
    """Device milliseconds under ``scope`` per unit of work, or None when
    the trace holds no such operation."""
    s = reading.trace.scope_s(scope)
    if s <= 0 or units <= 0:
        return None
    return s / units * 1e3


def idle_share(reading):
    t = reading.trace
    if t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
