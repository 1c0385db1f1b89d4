"""Device time of the ops traced inside the routed FFN's ``moe_experts``
scope (the grouped gate/up and down matmuls and the activation between them:
``deepspeed_tpu/moe/routed_ffn.py``) over device busy time. From the ops'
name stacks (``benchmark/op_scopes.py``); None where no op names the scope (a
dense model, or a program without it)."""

from benchmark import op_scopes


def value(trace, counters, cell):
    return None if trace is None else op_scopes.scope_share(trace, cell, "moe_experts")
