"""Time the core spends on collectives over the traced slice; mean over the
chips. Counted from the operations line of the ``.xplane.pb`` alone: a
synchronous all-gather, all-reduce, reduce-scatter, all-to-all or
collective-permute for as long as it runs, an asynchronous one for its start
and its done instructions, the done being the wait. That line is serial, so
nothing else runs meanwhile: all of this time is exposed. What an
asynchronous collective spends in flight under other work is
``collective_in_flight_share``."""

from benchmark.trace_reduce import is_collective, total


def value(trace, counters, cell):
    if trace is None or len(trace.devices) < 2:
        return None
    on_core = sum(total((ev.start, ev.end) for ev in d.leaves if is_collective(ev.name)) for d in trace.devices)
    return 100.0 * on_core / len(trace.devices) / trace.window_s
