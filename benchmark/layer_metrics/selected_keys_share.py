"""Share of the live keys that the sparse layers' queries attended: the sum of
``keys_chosen`` over the sum of ``keys_live`` on the ``serve.pack`` spans of
the traced slice (the scheduler's own record of a step: a query at position
``p`` has ``p + 1`` live keys and attends ``min(p + 1, index_topk)`` of them:
``deepspeed_tpu/inference/scheduler.py``). 100 while no row has passed
``index_topk`` keys: the indexer is then idle weight. None without a trace and
where no span carries the two counts (a model without sparse layers, the
parent)."""

from benchmark import program_spans


def value(trace, counters, cell):
    if trace is None:
        return None
    counts = program_spans.attr_values(trace, cell, "serve.pack", "keys_chosen", "keys_live")
    live = sum(l for _, l in counts)
    return 100.0 * sum(c for c, _ in counts) / live if live else None
