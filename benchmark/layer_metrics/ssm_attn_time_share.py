"""Device time of the ops traced inside the ``attention`` scope of a model
that also has state-space layers (its softmax layers' mixer: norm, q/k/v
projection, the ragged paged-attention kernel, the output projection) over
device busy time: what the one attention layer in ten costs beside
``ssm_time_share``, its heads of 64 (no whole lane tile) on the ragged
kernel's path without the page DMA. None for a model without state-space
layers (``softmax_attn_time_share`` and ``full_attn_time_share`` read the
scope for the models with linear and window layers), and where no op names the
scope."""

from benchmark import op_scopes


def value(trace, counters, cell):
    if trace is None or not counters["model"].get("num_ssm_layers"):
        return None
    return op_scopes.scope_share(trace, cell, "attention")
