"""Device time inside the ragged paged-attention kernel over device busy
time. From the ``.xplane.pb``. The kernel is found by signature, so the
reader first checks that every whole serving step in the slice holds exactly
one call a layer, and raises if not."""

from benchmark.kernels import ragged_paged_attention as k


def value(trace, counters, cell):
    if trace is None:
        return None
    dev = trace.devices[0]
    events = dev.checked_kernel_events(k.EVENTS, k.calls_per_step(counters["model"]["num_layers"]))
    return 100.0 * sum(ev.duration for ev in events["ragged"]) / dev.busy_s()
