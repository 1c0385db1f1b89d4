"""Device time of the ops traced inside the routed FFN's ``moe_shared`` scope
(the shared expert, one FFN that every token goes through beside its routed
experts: ``deepspeed_tpu/models/hybrid_moe.py::moe_ffn``) over device busy
time. From the ops' name stacks (``benchmark/op_scopes.py``); None where no
op names the scope (a model without a shared expert, a dense model)."""

from benchmark import op_scopes


def value(trace, counters, cell):
    return None if trace is None else op_scopes.scope_share(trace, cell, "moe_shared")
