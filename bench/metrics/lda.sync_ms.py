"""Device milliseconds per training iteration under ``lda.sync`` (the phi
all-reduce and the add of its result), averaged over the chips."""
from bench.metrics._common import per_unit_ms


def read(reading):
    return per_unit_ms(reading, "lda.sync", reading.window["iterations"])
