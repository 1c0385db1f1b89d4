"""Device time of the ops traced inside the routed FFN's ``moe_route`` scope
(router softmax and top-k, the sort of the assignments by expert, the gather
of the rows and their way back to token order with the gates:
``deepspeed_tpu/moe/routed_ffn.py``) over device busy time. From the ops'
name stacks (``benchmark/op_scopes.py``); None where no op names the scope."""

from benchmark import op_scopes


def value(trace, counters, cell):
    return None if trace is None else op_scopes.scope_share(trace, cell, "moe_route")
