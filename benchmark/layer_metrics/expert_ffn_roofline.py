"""The grouped expert matmuls' share of their roofline over the traced
steps: the least time for each step's counts (live assignments and experts
hit, the ``serve.settle`` span's attributes; bytes and operations in
``benchmark/kernels/grouped_expert_matmul.py``) over the device time of the
ops inside the ``moe_experts`` scope. A narrow step is bound by memory: every
expert that was hit has to be read once. None where the trace has no such
attribute or no such scope (a dense model, the parent)."""

from benchmark import op_scopes, program_spans
from benchmark.kernels import grouped_expert_matmul as k


def value(trace, counters, cell):
    if trace is None:
        return None
    steps = program_spans.attr_values(trace, cell, "serve.settle", "moe_assignments", "moe_experts_hit")
    m = counters["model"]
    spent = op_scopes.scope_self_time(op_scopes.of_cell(cell), trace.devices[0], "moe_experts")
    if not steps or not spent or not m.get("num_experts"):
        return None
    least = sum(
        k.min_seconds(a, hit, m["hidden_size"], m["expert_intermediate_size"], cell["peak"], m["expert_matrices"])[0] for a, hit in steps
    )
    return 100.0 * least / spent
