"""Host time a serving step spends admitting waiting requests
(``serve.admit``: the policy's choice, the slot and page reservation): median
over the traced slice's steps of the span's self time. From the program's own
spans in the ``.xplane.pb`` (``benchmark/program_spans.py``)."""

from benchmark import program_spans


def value(trace, counters, cell):
    return program_spans.phase_ms(trace, cell, "admit")
