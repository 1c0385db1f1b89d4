"""The ragged paged-attention kernel's share of its roofline in the
full-attention layers of a model that also has sliding-window layers, over the
traced steps: the least time for each step's live rows (every live key, 192 a
key and 128 a value for each of the full layers' KV heads; ``benchmark/
kernels/windowed_paged_attention.py`` with no window) times the full layers,
over the kernel's own device time in the calls traced inside the ``attention``
scope. Keys count at 192; the page stores 256 lanes, and the difference shows
as lost share. None for a model that does not tell its full layers from
window layers (every other model's ``attention`` scope belongs to the accepted
``ragged_attn_roofline``), and where no kernel call names the scope."""

from benchmark.kernels import windowed_paged_attention as k


def value(trace, counters, cell):
    m = counters["model"]
    if not m.get("num_window_layers"):
        return None
    return k.roofline(trace, counters, cell, "attention", m.get("num_full_layers", 0), m.get("full_kv_heads"), None)
