"""Live rows a serving dispatch carried: mean of the ``rows`` attribute of
the ``serve.dispatch`` spans in the traced slice (the program runs
``max_slots`` rows whatever this says: the rest are dead padding)."""

from benchmark import program_spans


def value(trace, counters, cell):
    if trace is None:
        return None
    rows = [r for (r,) in program_spans.attr_values(trace, cell, "serve.dispatch", "rows")]
    return sum(rows) / len(rows) if rows else None
