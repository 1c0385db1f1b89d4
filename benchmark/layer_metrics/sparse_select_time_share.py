"""Device time of the ops traced inside the ``sparse_select`` scope (the exact
top ``index_topk`` of a row's scores: a sort a decode row, the
``index_topk``-th largest and a count a chunk's query) over device busy time.
None for a model without sparse layers, and where no op names the scope (the
parent)."""

from benchmark.kernels import sparse_latent_attention as k


def value(trace, counters, cell):
    return k.time_share(trace, counters, cell, k.SCOPES["select"])
