"""Device time of the ops traced inside the engine's ``optimizer`` scope
(overflow check, gradient norm and clip, the update, the re-cast of the
parameters) over device busy time; mean over the chips. From the ops' name
stacks (``benchmark/op_scopes.py``)."""

from benchmark import op_scopes


def value(trace, counters, cell):
    return None if trace is None else op_scopes.scope_share(trace, cell, "optimizer")
