"""Device time of the ops traced inside the ``latent_attention`` scope (a
latent layer's whole mixer: norm, the two low-rank projections with their
norms, the rotary term, the absorbed product, the latent kernel over the row's
pages, the value and output projections: ``deepspeed_tpu/inference/
hybrid_decode.py``) over device busy time. From the ops' name stacks
(``benchmark/op_scopes.py``); None for a model without latent layers, and
where no op names the scope (the parent)."""

from benchmark import op_scopes


def value(trace, counters, cell):
    if trace is None or not counters["model"].get("num_latent_layers"):
        return None
    return op_scopes.scope_share(trace, cell, "latent_attention")
