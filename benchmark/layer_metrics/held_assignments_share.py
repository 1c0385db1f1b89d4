"""Share of a step's routed (token, expert) assignments that went to an
expert this chip holds: mean over the traced slice's steps of
``moe_assignments / moe_routed_assignments``, both attributes of
``serve.settle`` (the held are counted in the program, all routed are live
tokens x experts a token x layers). A chip that holds 40 of 320 experts gets
12.5% if the router spreads evenly; the rest is other chips' work, which this
one does not do. None where the span carries no such attributes (a model that
holds every expert its router chooses from, a dense model, the parent)."""

from benchmark import program_spans


def value(trace, counters, cell):
    if trace is None:
        return None
    steps = program_spans.attr_values(trace, cell, "serve.settle", "moe_assignments", "moe_routed_assignments")
    shares = [held / routed for held, routed in steps if routed]
    return 100.0 * sum(shares) / len(shares) if shares else None
