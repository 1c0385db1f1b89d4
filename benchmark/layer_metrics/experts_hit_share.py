"""Share of a step's experts (layers x experts a layer) that received at
least one live token: mean over the traced slice's steps of the
``moe_experts_hit`` attribute of ``serve.settle`` over ``num_layers x
num_experts``. 16 decode rows of 8 choices over 64 experts hit 87% if the
router spreads evenly. None where the span carries no such attribute (a
dense model, the parent)."""

from benchmark import program_spans


def value(trace, counters, cell):
    if trace is None:
        return None
    hits = [h for (h,) in program_spans.attr_values(trace, cell, "serve.settle", "moe_experts_hit")]
    m = counters["model"]
    if not hits or not m.get("num_experts"):
        return None
    return 100.0 * sum(hits) / (len(hits) * m["num_layers"] * m["num_experts"])
