"""Device time of the ops traced inside the ``attention`` scope of a model
that also has sliding-window layers (a full-attention layer's mixer: norm,
projections, rotary term, the ragged kernel over the row's pages, the output
projection) over device busy time. From the ops' name stacks
(``benchmark/op_scopes.py``); None for a model that does not tell its full
layers from window layers, and where no op names the scope."""

from benchmark import op_scopes


def value(trace, counters, cell):
    if trace is None or not counters["model"].get("num_window_layers"):
        return None
    return op_scopes.scope_share(trace, cell, "attention")
