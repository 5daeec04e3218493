"""Device milliseconds per training iteration under ``lda.phi_delta``."""
from bench.metrics._common import per_unit_ms


def read(reading):
    return per_unit_ms(reading, "lda.phi_delta", reading.window["iterations"])
