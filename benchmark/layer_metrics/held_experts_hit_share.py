"""Share of a step's HELD experts (routed layers x experts held a layer) that
received at least one live token: mean over the traced slice's steps of the
``moe_experts_hit`` attribute of ``serve.settle`` over ``num_moe_layers x
num_experts``. The accepted ``experts_hit_share`` divides by every layer; a
model whose leading layers have a dense FFN routes in fewer, and names them
``num_moe_layers``. 64 decode rows of 8 choices over 256 experts bring a held
expert 2 tokens a step on average and hit 86.5% of them if the router spreads
evenly (1 - (1 - 1/256)^512). None where the span carries no such attribute
(a dense model, the parent) and for a model that does not say how many of its
layers route."""

from benchmark import program_spans


def value(trace, counters, cell):
    m = counters["model"]
    if trace is None or not m.get("num_moe_layers") or not m.get("num_experts"):
        return None
    hits = [h for (h,) in program_spans.attr_values(trace, cell, "serve.settle", "moe_experts_hit")]
    if not hits:
        return None
    return 100.0 * sum(hits) / (len(hits) * m["num_moe_layers"] * m["num_experts"])
