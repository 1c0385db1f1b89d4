"""Time in which a collective is on the core or, if asynchronous, in flight
between its start and its done, over the traced slice: how much transfer
there is to hide, whether or not it is hidden. Needs the ``Async XLA Ops``
line, which this runtime writes on the first chip's plane only (PR 22): the
mean is over the chips that carry it, and without one there is nothing to
read. On GPT-2 XL two collective-permutes of the embedding's shards are in
flight for most of every step, so this reads ~80% while the core spends 5%
on collectives."""

from benchmark.trace_reduce import is_collective, total


def value(trace, counters, cell):
    if trace is None:
        return None
    carrying = [d for d in trace.devices if any(is_collective(ev.name) for ev in d.async_ops)]
    if not carrying:
        return None
    return 100.0 * sum(total(d.collective_intervals()) for d in carrying) / len(carrying) / trace.window_s
