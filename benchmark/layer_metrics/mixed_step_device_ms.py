"""Median device time of one execution of the mixed ragged program (width =
the prefill chunk: at least one row prefills), from the device operations
inside each ``server_step`` annotation of the traced slice."""

import statistics

from benchmark import serve_steps as h


def value(trace, counters, cell):
    if trace is None:
        return None
    device_s = [busy for _, mixed, busy in h.steps(trace, counters) if mixed]
    return 1e3 * statistics.median(device_s) if device_s else None
