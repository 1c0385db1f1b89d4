"""Device time of the ops traced inside the ``sparse_attend`` scope (the
gather of a decode row's chosen entries and the absorbed attention over them;
a chunk's masked walk of its row's pages) over device busy time. None for a
model without sparse layers, and where no op names the scope (the parent)."""

from benchmark.kernels import sparse_latent_attention as k


def value(trace, counters, cell):
    return k.time_share(trace, counters, cell, k.SCOPES["attend"])
