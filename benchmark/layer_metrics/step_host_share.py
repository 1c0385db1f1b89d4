"""The part of ``server.step()`` in which the device did nothing: 1 - device
busy inside the ``server_step`` annotations / their total length, over the
traced slice. Waiting for arrivals is outside the annotations and not
counted. Half depth (16 of 32 layers) makes this share larger than in a
deployment of the full model."""

from benchmark import serve_steps as h


def value(trace, counters, cell):
    if trace is None:
        return None
    steps = h.steps(trace, counters)
    length = sum(ev.duration for ev, _, _ in steps)
    return 100.0 * (1.0 - sum(busy for _, _, busy in steps) / length) if length else None
