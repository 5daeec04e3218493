"""Mean documents per executed batch over the window, from the engine's
counters."""


def read(reading):
    c = reading.window["counters"]
    if c["batches"] <= 0:
        return None
    return c["requests"] / c["batches"]
