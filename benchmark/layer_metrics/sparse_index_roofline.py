"""The indexer's scores' share of their roofline over the traced steps: the
least time for each step's live rows (a row reads its ``kv`` live indexer keys
of 128 numbers once, 64 heads x 2 x 128 operations a pair;
``benchmark/kernels/sparse_latent_attention.py``) times the sparse layers,
over the device time of the ops traced inside the ``sparse_index_scores``
scope. None without a trace, a rows log, sparse layers or such ops (the
parent)."""

from benchmark.kernels import sparse_latent_attention as k


def value(trace, counters, cell):
    m = counters["model"]
    least = lambda rows: k.min_seconds(*k.index_ops_and_bytes(rows, m["index_heads"], m["index_head_dim"]), cell["peak"])
    return k.roofline(trace, counters, cell, k.SCOPES["index"], least)
