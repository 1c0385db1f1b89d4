"""The ragged paged-attention kernel's share of its roofline in the
sliding-window layers of a model whose two kinds of attention layer have
query-head counts of their own, over the traced steps: the least time for each
step's live rows (their ``(q_len, kv_len)`` as the driver logged them: a row
reads the newest ``window + q - 1`` keys at most, for each of the window
layers' KV heads, and computes for the WINDOW layers' query heads,
``window_heads``; ``benchmark/kernels/windowed_paged_attention.py``) times the
window layers, over the kernel's own device time in the calls traced inside
the ``window_attention`` scope. The reckoning is the accepted
``window_attn_roofline``'s (``roofline()``), which reads ONE ``num_heads`` for
both kinds and is not asked of such a model: here it is given the model's
shape with the window layers' heads in that place. None for a model that does
not state ``window_heads``, and where no kernel call names the scope (the
parent)."""

from benchmark.kernels import windowed_paged_attention as k


def value(trace, counters, cell):
    m = counters["model"]
    if not m.get("window_heads"):
        return None
    as_window = {**counters, "model": {**m, "num_heads": m["window_heads"]}}
    return k.roofline(trace, as_window, cell, "window_attention", m.get("num_window_layers", 0), m.get("window_kv_heads"), m.get("window"))
