"""The latent paged-attention kernel's share of its roofline over the traced
steps: the least time for each step's live rows (their ``(q_len, kv_len)`` as
the driver logged them: a row reads its ``kv`` entries of 576 numbers ONCE,
20 heads x 2 x (576 + 512) operations a pair; ``benchmark/kernels/
latent_paged_attention.py``) times the latent layers, over the kernel's OWN
device time in the calls traced inside the ``latent_attention`` scope. An
entry counts at 576, what the mathematics needs; a page stores 640 lanes, and
the difference shows as lost share. None without a trace, a rows log, latent
layers or such calls (the parent)."""

from benchmark.kernels import latent_paged_attention as k


def value(trace, counters, cell):
    m = counters["model"]
    if trace is None or not m.get("num_latent_layers") or not counters.get("rows_log"):
        return None
    _, spent = k.scope_and_kernel_time(trace, cell)
    if not spent:
        return None
    least = sum(
        k.min_seconds(step["rows"], m["num_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"], cell["peak"])[0]
        for step in counters["rows_log"]
    )
    return 100.0 * m["num_latent_layers"] * least / spent
