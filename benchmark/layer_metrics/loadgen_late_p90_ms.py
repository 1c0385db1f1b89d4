"""90th percentile of (instant the driver submitted a request - instant it
was due) over the measured requests: a starved generator is not a fast
server."""

from benchmark.loadgen import percentile


def value(trace, counters, cell):
    if "late_ms" not in counters:
        return None
    return percentile(counters["late_ms"], 90)
