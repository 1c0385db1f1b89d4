"""The ring attention's share of its roofline in the window layers whose ring
holds latents, over the traced steps: the least time for each step's live rows
(a row reads the newest ``window + q - 1`` entries of 1,088 numbers at most,
once, 64 heads x 2 x (1,088 + 1,024) operations a pair;
``benchmark/kernels/ring_latent_attention.py``) times those layers, over the
device time of the ops traced inside the ``ring_latent_attend`` scope. None
without a trace, a rows log, such layers or such ops (the parent)."""

from benchmark.kernels import ring_latent_attention as k
from benchmark.kernels.sparse_latent_attention import scope_time


def value(trace, counters, cell):
    m = counters["model"]
    if trace is None or not m.get("num_window_latent_layers") or not counters.get("rows_log"):
        return None
    spent = scope_time(trace, cell, k.SCOPE)
    if not spent:
        return None
    least = sum(
        k.min_seconds(step["rows"], m["window_heads"], m["window_kv_lora_rank"], m["window_qk_rope_head_dim"], m["window"], cell["peak"])
        for step in counters["rows_log"]
    )
    return 100.0 * m["num_window_latent_layers"] * least / spent
