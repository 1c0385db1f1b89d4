"""1 - (union of the intervals in which an operation ran on the device) /
the traced slice; mean over the chips used. From the ``.xplane.pb`` only."""


def value(trace, counters, cell):
    return None if trace is None else 100.0 * trace.idle_share()
