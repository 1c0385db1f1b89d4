"""Device time of the ops traced inside the ``window_latent_attention`` scope
(a window layer's whole latent mixer: norm, the two low-rank projections with
their norms and rescales, the rotary term, the absorbed product, the
attention over the slot's ring of latents, the value projection, the gate a
head and the output projection: ``deepspeed_tpu/inference/hybrid_decode.py``)
over device busy time. None for a model without such layers, and where no op
names the scope (the parent)."""

from benchmark import op_scopes
from benchmark.kernels import ring_latent_attention as k


def value(trace, counters, cell):
    if trace is None or not counters["model"].get("num_window_latent_layers"):
        return None
    return op_scopes.scope_share(trace, cell, k.MIXER_SCOPE)
