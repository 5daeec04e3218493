"""Device milliseconds per training iteration under ``lda.plan/theta``:
the theta rebuild (the scatter XLA expands into a sort and a fusion, and
the theta sync)."""
from bench.metrics._common import per_unit_ms


def read(reading):
    return per_unit_ms(reading, "lda.plan/theta",
                       reading.window["iterations"])
