"""Device time of the ops traced inside the ``attention`` scope of a model
that also has gated short-convolution layers (its softmax layers' mixer: norm,
q/k/v projection, the norm a head on q and k, the rotation, the ragged
paged-attention kernel, the output projection) over device busy time: what the
one attention layer in four costs beside ``conv_mixer_time_share``, its heads
of 64 two to a lane tile. None for a model without conv layers
(``ssm_attn_time_share``, ``softmax_attn_time_share`` and
``full_attn_time_share`` read the scope for the models with state-space,
linear and window layers), and where no op names the scope."""

from benchmark import op_scopes


def value(trace, counters, cell):
    if trace is None or not counters["model"].get("num_conv_layers"):
        return None
    return op_scopes.scope_share(trace, cell, "attention")
