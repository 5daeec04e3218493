"""The whole iteration's share of the chip's peak: the least time the chip
could take for the sweep, the plan and the count update, counted from the
data (``bench.work.lda_sample``), over the window's mean iteration time.
For LDA the byte bound is the larger of the two."""
from bench.work import lda_sample as work


def read(reading):
    w = reading.work
    if not w or reading.window["iterations"] <= 0:
        return None
    ops = sum(w[k][0] for k in ("sampler", "plan", "count_update"))
    byts = sum(w[k][1] for k in ("sampler", "plan", "count_update"))
    per_iter = reading.trace.window_s / reading.window["iterations"]
    return 100.0 * work.bound_seconds(ops, byts, reading.peaks) / per_iter
