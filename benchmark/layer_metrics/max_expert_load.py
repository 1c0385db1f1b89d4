"""The largest number of live tokens any one expert of any layer received
in a step: mean over the traced slice's steps of the ``moe_max_expert_load``
attribute of ``serve.settle``. 128 assignments spread evenly over 64 experts
give about 6; a group past the grouped matmul's row tile of 128 costs its
expert's weights a second read. None where the span carries no such
attribute (a dense model, the parent)."""

from benchmark import program_spans


def value(trace, counters, cell):
    if trace is None:
        return None
    loads = [m for (m,) in program_spans.attr_values(trace, cell, "serve.settle", "moe_max_expert_load")]
    return sum(loads) / len(loads) if loads else None
