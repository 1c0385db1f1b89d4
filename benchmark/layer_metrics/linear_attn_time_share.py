"""Device time of the ops traced inside the ``linear_attention`` scope (a
linear layer's mixer: norm, projections, decay and gates, the short
convolution, the delta-rule recurrence, the gated output projection:
``deepspeed_tpu/inference/hybrid_decode.py``) over device busy time. From the
ops' name stacks (``benchmark/op_scopes.py``); None for a model without
linear layers, and where no op names the scope (the parent)."""

from benchmark import op_scopes


def value(trace, counters, cell):
    if trace is None or not counters["model"].get("num_linear_layers"):
        return None
    return op_scopes.scope_share(trace, cell, "linear_attention")
