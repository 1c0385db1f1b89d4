"""Median device time of one execution of the narrow ragged program (width
1: every row decodes), from the device operations inside each
``server_step`` annotation of the traced slice."""

import statistics

from benchmark import serve_steps as h


def value(trace, counters, cell):
    if trace is None:
        return None
    device_s = [busy for _, mixed, busy in h.steps(trace, counters) if not mixed]
    return 1e3 * statistics.median(device_s) if device_s else None
