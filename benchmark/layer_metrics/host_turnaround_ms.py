"""The host's time on the serving step's critical path: median over ``seq``
of ``serve.enqueue{seq = n + 1}.start - serve.fetch{seq = n}.end``, both
whole inside the slice, a pair with a drain's settle between them left out.
The host's own clock and nothing else. It holds everything of the program's
and of its caller's that the device waits for: the wait's wake-up tail inside
the span, ``step()``'s end, the caller's loop, the gauges, ``serve.admit``, a
second pack when someone was admitted, ``_dispatch``'s Python before the
call. What the phase readers (``step_pack_ms``, ``step_settle_ms``, most of
``step_dispatch_ms``) time runs behind the device since PR 35 and is not in
it. The same check as ``exec_gap_ms`` runs first (``benchmark/step_seq.py``).
None without ``serve.enqueue`` spans."""

from benchmark import program_spans, step_seq


def value(trace, counters, cell):
    if trace is None:
        return None
    found = step_seq.steps(trace, program_spans.of_cell(trace, cell))
    return step_seq.median_ms(step_seq.turnarounds(found).values()) if found else None
