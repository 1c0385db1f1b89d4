"""Shared by the serving step readers: the ``server_step`` annotations of
the traced slice paired, in order, with the driver's log of which program
each step ran, and the device time inside each."""


def steps(trace, counters):
    spans = sorted(trace.host_spans("server_step"), key=lambda ev: ev.start)
    log = counters.get("rows_log") or []
    if len(spans) != len(log):
        raise ValueError(f"{len(spans)} server_step annotations in the slice but {len(log)} steps logged")
    return [(ev, entry["mixed"], trace.busy_inside((ev.start, ev.end))) for ev, entry in zip(spans, log)]
