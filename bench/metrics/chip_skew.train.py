"""How far the slowest chip holds the others at the phi all-reduce:
100 (max / mean - 1) over the chips of each chip's device time outside
``lda.sync`` and ``lda.stats`` (the iteration's collectives, where the
others wait for it).  Nothing to read on one chip."""
from bench.trace import _scope_re

COLLECTIVES = ("lda.sync", "lda.stats")


def read(reading):
    t = reading.trace
    if t.num_devices < 2:
        return None
    pats = [_scope_re(s) for s in COLLECTIVES]
    busy = [0] * t.num_devices
    for op in t.ops:
        if any(p.search(op.text) for p in pats):
            continue
        s, e = max(op.start, t.start), min(op.end, t.end)
        busy[op.device] += max(e - s, 0)
    mean = sum(busy) / len(busy)
    if mean <= 0:
        return None
    return 100.0 * (max(busy) / mean - 1.0)
