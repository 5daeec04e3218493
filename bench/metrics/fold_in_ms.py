"""Mean device milliseconds per executed batch under ``serve.sweeps``."""
from bench.metrics._common import per_unit_ms


def read(reading):
    return per_unit_ms(reading, "serve.sweeps", reading.window["batches"])
