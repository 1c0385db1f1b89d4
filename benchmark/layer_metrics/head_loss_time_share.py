"""Device time of the ops traced inside the model's ``head_loss`` scope
(final norm, LM head, cross entropy; forward and backward) over device busy
time; mean over the chips. From the ops' name stacks
(``benchmark/op_scopes.py``)."""

from benchmark import op_scopes


def value(trace, counters, cell):
    return None if trace is None else op_scopes.scope_share(trace, cell, "head_loss")
