"""Device milliseconds per training iteration under ``lda.plan/ell``: the
top-k that orders each document's topics into its ELL row."""
from bench.metrics._common import per_unit_ms


def read(reading):
    return per_unit_ms(reading, "lda.plan/ell", reading.window["iterations"])
