"""The ragged paged-attention kernel's share of its roofline over the traced
steps: the least time for each step's live rows (their ``(q_len, kv_len)``
as the driver logged them, times the layers; bytes and operations in
``benchmark/kernels/ragged_paged_attention.py``) over the kernel's time in
the slice. Decode rows are bound by memory: the kernel has to read every
live key and value once. The same count check as ``ragged_attn_time_share``
comes first."""

from benchmark.kernels import ragged_paged_attention as k


def value(trace, counters, cell):
    if trace is None:
        return None
    m = counters["model"]
    least = sum(k.min_seconds(step["rows"], m["num_heads"], m["num_kv_heads"], m["head_dim"], cell["peak"])[0] for step in counters["rows_log"])
    least *= m["num_layers"]
    events = trace.devices[0].checked_kernel_events(k.EVENTS, k.calls_per_step(m["num_layers"]))
    return 100.0 * least / sum(ev.duration for ev in events["ragged"])
