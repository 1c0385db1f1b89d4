"""The synchronous part of a launch: median self time of ``serve.enqueue``,
the span around the step's jitted call alone (flattening and checking the
arguments, the whole parameter tree among them, and the runtime's enqueue),
which the device cannot start before. From the program's own spans in the
``.xplane.pb`` (``benchmark/program_spans.py``); None without such a span."""

from benchmark import program_spans, step_seq


def value(trace, counters, cell):
    if trace is None:
        return None
    return step_seq.median_ms(program_spans.self_seconds(program_spans.of_cell(trace, cell), step_seq.ENQUEUE))
