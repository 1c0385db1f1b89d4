"""The delta-rule recurrence's share of its roofline over the traced steps:
the least time for each step's live rows (their ``q_len`` as the driver
logged them, times the linear layers: state read and written once a row,
plus each token's q, k, v, decay, b and output; bytes and operations in
``benchmark/kernels/kda_recurrence.py``) over the device time of the ops
inside the ``kda_recurrence`` scope (convolution, norms, the ``kda_decode``
kernel or the chunkwise form). A decode step is bound by memory: every live
row's state has to cross the chip's memory bus twice. None for a model
without linear layers, and where the trace has no such scope (the parent)."""

from benchmark import op_scopes
from benchmark.kernels import kda_recurrence as k


def value(trace, counters, cell):
    m = counters["model"]
    if trace is None or not m.get("num_linear_layers") or not counters.get("rows_log"):
        return None
    spent = op_scopes.scope_self_time(op_scopes.of_cell(cell), trace.devices[0], "kda_recurrence")
    if not spent:
        return None
    least = sum(
        k.min_seconds(step["rows"], m["linear_heads"], m["linear_head_dim"], cell["peak"], m["linear_conv_kernel"])[0]
        for step in counters["rows_log"]
    )
    return 100.0 * m["num_linear_layers"] * least / spent
