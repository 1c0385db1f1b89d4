"""Share of the KV pool's allocatable pages that live requests held at the
top of a serving step: mean of ``pages_in_use / pages_total`` over the
``serve.step`` spans in the traced slice."""

from benchmark import program_spans


def value(trace, counters, cell):
    if trace is None:
        return None
    shares = [used / total for used, total in program_spans.attr_values(trace, cell, "serve.step", "pages_in_use", "pages_total")]
    return 100.0 * sum(shares) / len(shares) if shares else None
