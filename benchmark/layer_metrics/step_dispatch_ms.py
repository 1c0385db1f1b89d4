"""Host time a step spends building and enqueueing its program: median over
the traced slice's steps of the self time of ``serve.dispatch`` in a serving
cell and of ``train.dispatch`` in a training cell (the span family follows
the configuration's ``engine.kind``). The device's time is not in it: the
enqueue returns at once. From the program's own spans in the ``.xplane.pb``
(``benchmark/program_spans.py``)."""

from benchmark import program_spans


def value(trace, counters, cell):
    return program_spans.phase_ms(trace, cell, "dispatch")
