"""device layer: the share of the traced window in which no operation ran
on the device (``xtrace``: 1 - union of op intervals / window), in %."""


def read(m):
    if m.trace is None or m.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - m.trace.busy_s / m.trace.window_s)
