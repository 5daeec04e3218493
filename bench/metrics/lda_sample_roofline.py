"""The sampler's share of its roofline: the least time the chip could
take for the sweep's Table 1 work (``bench.work.lda_sample.sampler``),
over the device time per iteration under ``lda.sample``."""
from bench.work import lda_sample as work


def read(reading):
    s = reading.trace.scope_s("lda.sample")
    if s <= 0:
        return None
    per_iter = s / reading.window["iterations"]
    bound = work.bound_seconds(*reading.work["sampler"], reading.peaks)
    return 100.0 * bound / per_iter
