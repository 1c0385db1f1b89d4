"""The ragged paged-attention kernel's share of its roofline in the
sliding-window layers over the traced steps: the least time for each step's
live rows (their ``(q_len, kv_len)`` as the driver logged them: a row reads
the newest ``window + q - 1`` keys at most, 192 a key and 128 a value for
each of the window layers' KV heads; ``benchmark/kernels/
windowed_paged_attention.py``) times the window layers, over the kernel's own
device time in the calls traced inside the ``window_attention`` scope. Keys
count at 192, what the mathematics needs; the page stores 256 lanes, and the
difference shows as lost share. None for a model without window layers, and
where no kernel call names the scope (the parent)."""

from benchmark.kernels import windowed_paged_attention as k


def value(trace, counters, cell):
    m = counters["model"]
    return k.roofline(trace, counters, cell, "window_attention", m.get("num_window_layers", 0), m.get("window_kv_heads"), m.get("window"))
