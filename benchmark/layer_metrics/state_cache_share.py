"""Which of a model's two caches the live rows' bytes are in: mean over the
``serve.step`` spans in the traced slice of ``state_bytes_in_use /
(state_bytes_in_use + latent_bytes_in_use)``, the slots in use times a slot's
recurrent states and convolution tails over the linear layers against the
pages in use times a page's entries over the latent layers
(``kv_pool.PagePool.cache_bytes``). The state's part is constant a row, the
latent's grows with its length: the share falls as contexts grow. None where
the spans carry no such attributes (a model with neither cache, the parent)
and for the steps of an empty server."""

from benchmark import program_spans


def value(trace, counters, cell):
    if trace is None:
        return None
    both = program_spans.attr_values(trace, cell, "serve.step", "state_bytes_in_use", "latent_bytes_in_use")
    shares = [state / (state + latent) for state, latent in both if state + latent > 0]
    return 100.0 * sum(shares) / len(shares) if shares else None
