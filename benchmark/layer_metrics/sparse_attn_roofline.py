"""The chosen-keys attention's share of its roofline over the traced steps:
the least time for each step's live rows, the LARGER of the chosen entries'
bytes over the bandwidth and their operations over the peak (a decode row of
128 heads sits on the ridge: ``benchmark/kernels/sparse_latent_attention.py``),
times the sparse layers, over the device time of the ops traced inside the
``sparse_attend`` scope. An entry counts at 576 numbers and a row at the
entries it attends: the gather's second pass over them and a page's 640 lanes
show as lost share. None without a trace, a rows log, sparse layers or such
ops (the parent)."""

from benchmark.kernels import sparse_latent_attention as k


def value(trace, counters, cell):
    m = counters["model"]
    least = lambda rows: k.min_seconds(
        *k.attend_ops_and_bytes(rows, m["num_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"], m["index_topk"]), cell["peak"]
    )
    return k.roofline(trace, counters, cell, k.SCOPES["attend"], least)
