"""Device milliseconds per training iteration under ``lda.plan``."""
from bench.metrics._common import per_unit_ms


def read(reading):
    return per_unit_ms(reading, "lda.plan", reading.window["iterations"])
