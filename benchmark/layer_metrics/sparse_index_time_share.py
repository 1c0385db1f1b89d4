"""Device time of the ops traced inside the ``sparse_index`` scope (a sparse
latent layer's indexer: its queries from the query's low rank, its key and its
weight a head from the normed input, their rotary term) and the
``sparse_index_scores`` scope (the scores of every query against its row's
live indexer keys, read block by block of the page table:
``deepspeed_tpu/ops/transformer/sparse_latent_attention.py``) over device busy
time: what choosing keys costs before any is chosen. None for a model without
sparse layers, and where no op names either scope (the parent)."""

from benchmark.kernels import sparse_latent_attention as k


def value(trace, counters, cell):
    return k.time_share(trace, counters, cell, "sparse_index", k.SCOPES["index"])
