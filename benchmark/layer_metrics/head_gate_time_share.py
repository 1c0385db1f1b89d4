"""Device time of the ops traced inside the ``head_gate`` scope (a full or
window layer's output gate: the ``[tokens, H] x [H, heads]`` projection of the
normed input, its sigmoid and the product with the kernel's output a head:
``deepspeed_tpu/models/hybrid_moe.py::output_gate``) over device busy time: what
gating every head costs beside the mixer it sits in. From the ops' name stacks
(``benchmark/op_scopes.py``); None without a trace and where no op names the
scope (a model without the gate, the parent)."""

from benchmark import op_scopes


def value(trace, counters, cell):
    if trace is None:
        return None
    return op_scopes.scope_share(trace, cell, "head_gate")
