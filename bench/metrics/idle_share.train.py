"""Share of the traced window in which no operation ran on the device."""
from bench.metrics._common import idle_share


def read(reading):
    return idle_share(reading)
