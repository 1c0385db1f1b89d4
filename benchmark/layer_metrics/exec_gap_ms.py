"""The gap between two executions of the serving step, where it happens:
median over the slice's consecutive pairs of WHOLE executions on device 0's
``XLA Modules`` line (those of the programs the slice's ``serve.enqueue``
spans name) of ``next.start - this.end``. The device's own clock and nothing
else: no ``server_step`` annotation, no ``rows_log``, and ``clock_shift`` only
in choosing which executions the slice holds and in the check that pairs them
with the spans (``benchmark/step_seq.py``, which raises where the device ran
another program than the paired span names or where, between two pairs,
executions and enqueues are not equally many; a step whose execution a host
stall put beyond the pairing's reach is paired by order and raises nothing).
The two small programs between two steps (the token feed, the gather of the
next tokens; a few microseconds) run inside the gap. A median, so the few
gaps of a server that emptied, or of a host that stood still, are samples
that do not move it, and it stays true for a server whose call returns while
the device runs. None without ``serve.enqueue`` spans."""

from benchmark import program_spans, step_seq


def value(trace, counters, cell):
    if trace is None:
        return None
    found = step_seq.steps(trace, program_spans.of_cell(trace, cell))
    return step_seq.median_ms(step_seq.exec_gaps(trace, found)) if found else None
