"""Device time of the ops traced inside the ``conv_mixer`` scope (a gated
short-convolution layer's mixer: norm, the input projection, the gated
product, the convolution over the row's tail, the tail's hand-over, the output
gate and projection: ``deepspeed_tpu/inference/hybrid_decode.py``) over device
busy time. From the ops' name stacks (``benchmark/op_scopes.py``); None for a
model without conv layers, and where no op names the scope (the parent)."""

from benchmark import op_scopes


def value(trace, counters, cell):
    if trace is None or not counters["model"].get("num_conv_layers"):
        return None
    return op_scopes.scope_share(trace, cell, "conv_mixer")
