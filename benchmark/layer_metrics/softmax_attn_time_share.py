"""Device time of the ops traced inside the ``attention`` scope of a model
that also has linear layers (its softmax layers' mixer: norm, q/k/v
projection, the ragged paged-attention kernel, the output gate and
projection) over device busy time: what the one softmax layer in four costs
beside ``linear_attn_time_share``. None for a model without linear layers
(its attention share is ``ragged_attn_time_share``'s business), and where no
op names the scope."""

from benchmark import op_scopes


def value(trace, counters, cell):
    if trace is None or not counters["model"].get("num_linear_layers"):
        return None
    return op_scopes.scope_share(trace, cell, "attention")
