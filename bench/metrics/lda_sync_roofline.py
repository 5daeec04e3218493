"""The phi sync's share of its roofline: the least time a ring all-reduce
of the iteration's phi delta takes over the chip's interconnect
(``bench.work.lda_sync``), over the device time per iteration under
``lda.sync``, averaged over the chips."""
from bench.work import lda_sync


def read(reading):
    sync = reading.work.get("sync")
    s = reading.trace.scope_s("lda.sync")
    if not sync or s <= 0 or reading.window["iterations"] <= 0:
        return None
    per_iter = s / reading.window["iterations"]
    bound = lda_sync.bound_seconds(sync["payload_bytes"], sync["chips"],
                                   sync["device_kind"])
    return 100.0 * bound / per_iter
