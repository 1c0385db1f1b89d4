"""Device time inside the flash-attention kernels (forward, dq, dkv) over
device busy time; mean over the chips. From the ``.xplane.pb``. The kernels
are found by signature, so the reader first checks that every whole step in
the slice holds exactly the calls the model's layers need, and raises if not."""

from benchmark.kernels import flash_attention as k


def value(trace, counters, cell):
    if trace is None:
        return None
    calls = k.calls_per_step(counters["model"]["num_layers"], counters["model"]["remat"])
    shares = []
    for dev in trace.devices:
        events = dev.checked_kernel_events(k.EVENTS, calls)
        shares.append(sum(ev.duration for evs in events.values() for ev in evs) / dev.busy_s())
    return 100.0 * sum(shares) / len(shares)
