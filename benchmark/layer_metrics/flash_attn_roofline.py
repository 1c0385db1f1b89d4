"""The flash-attention kernels' share of their roofline: the least time the
chip could take for the traced invocations (the larger of operations over
peak bf16 FLOP/s and bytes over peak HBM bytes/s, from shapes, in
``benchmark/kernels/flash_attention.py``) over the time they took. Which peak
bounds each kernel is printed by ``bound()``: at T 1024, D 64 the forward is
bound by compute (0.68 us against 0.64 us of bytes per head). The same count
check as ``flash_attn_time_share`` comes first."""

from benchmark.kernels import flash_attention as k


def shapes(counters):
    return counters["rows_per_chip"] * counters["model"]["num_heads"], counters["seq_len"], counters["model"]["head_dim"]


def bound(counters, peak):
    return {kind: k.min_seconds(kind, *shapes(counters), peak)[1] for kind in k.EVENTS}


def value(trace, counters, cell):
    if trace is None:
        return None
    calls = k.calls_per_step(counters["model"]["num_layers"], counters["model"]["remat"])
    least = took = 0.0
    for dev in trace.devices:
        for kind, events in dev.checked_kernel_events(k.EVENTS, calls).items():
            least += len(events) * k.min_seconds(kind, *shapes(counters), cell["peak"])[0]
            took += sum(ev.duration for ev in events)
    return 100.0 * least / took
