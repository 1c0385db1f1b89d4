"""Host time a serving step spends on the per-row bookkeeping after its one
fetch (``serve.settle``: advance, emit, publish, finish): median over the
traced slice's steps of the span's self time. The blocked ``serve.fetch``
before it, which is the wait for the device, is not in it. From the
program's own spans in the ``.xplane.pb`` (``benchmark/program_spans.py``)."""

from benchmark import program_spans


def value(trace, counters, cell):
    return program_spans.phase_ms(trace, cell, "settle")
