"""Device time of the ops traced inside the ``ssm_mixer`` scope (a
state-space layer's mixer: norm, the input projection, ``dt``'s softplus, the
convolution, the recurrence, the gated norm and the output projection:
``deepspeed_tpu/inference/hybrid_decode.py``) over device busy time. From the
ops' name stacks (``benchmark/op_scopes.py``); None for a model without
state-space layers, and where no op names the scope (the parent)."""

from benchmark import op_scopes


def value(trace, counters, cell):
    if trace is None or not counters["model"].get("num_ssm_layers"):
        return None
    return op_scopes.scope_share(trace, cell, "ssm_mixer")
