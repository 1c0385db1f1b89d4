"""Device time of the ops traced inside the ``window_attention`` scope (a
sliding-window layer's mixer: norm, projections, rotary term, the ragged
kernel over the slot's page ring with its sinks, the output projection:
``deepspeed_tpu/inference/hybrid_decode.py``) over device busy time. From the
ops' name stacks (``benchmark/op_scopes.py``); None for a model without
window layers, and where no op names the scope (the parent)."""

from benchmark import op_scopes


def value(trace, counters, cell):
    if trace is None or not counters["model"].get("num_window_layers"):
        return None
    return op_scopes.scope_share(trace, cell, "window_attention")
