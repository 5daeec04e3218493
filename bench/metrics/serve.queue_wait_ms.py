"""Mean queue wait of the window's requests, submit to the start of their
batch, from the engine's own histogram (its sum and count cover every
request; the difference over the window is taken)."""


def read(reading):
    c = reading.window["counters"]
    if c["queue_wait_n"] <= 0:
        return None
    return c["queue_wait_ms_sum"] / c["queue_wait_n"]
