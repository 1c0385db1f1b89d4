"""Device time of the ops traced inside the ``latent_attention`` scope but
OUTSIDE the latent kernel's own calls (the norm, the two low-rank projections
and their norms, the rotary term, the absorbed product ``q_nope Wk_b^T``, the
gathers that lay a window out for the kernel, ``(P c_kv) Wv_b`` and the output
projection) over device busy time: what the mixer costs beside reading the
pages. None for a model without latent layers, and where no op names the
scope (the parent)."""

from benchmark.kernels import latent_paged_attention as k


def value(trace, counters, cell):
    if trace is None or not counters["model"].get("num_latent_layers"):
        return None
    scope, kernel = k.scope_and_kernel_time(trace, cell)
    if not scope:
        return None
    return 100.0 * (scope - kernel) / trace.devices[0].busy_s()
