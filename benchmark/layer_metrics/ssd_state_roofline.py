"""The state-space recurrence's share of its roofline over the traced steps:
the least time for each step's live rows (their ``q_len`` as the driver logged
them, times the state-space layers: state and tail read and written once a
row, plus each token's ``[x ; B ; C]``, ``dt`` and output; bytes and operations
in ``benchmark/kernels/ssd_recurrence.py``) over the device time of the ops
inside the ``ssd_recurrence`` scope (the convolution, the ``ssd_decode`` kernel
or the chunk form). A decode step is bound by memory: every live row's state
has to cross the chip's memory bus twice. None for a model without state-space
layers, and where the trace has no such scope (the parent)."""

from benchmark import op_scopes
from benchmark.kernels import ssd_recurrence as k


def value(trace, counters, cell):
    m = counters["model"]
    if trace is None or not m.get("num_ssm_layers") or not counters.get("rows_log"):
        return None
    spent = op_scopes.scope_self_time(op_scopes.of_cell(cell), trace.devices[0], "ssd_recurrence")
    if not spent:
        return None
    least = sum(
        k.min_seconds(step["rows"], m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"], m["ssm_conv_channels"], cell["peak"], m["ssm_conv_kernel"])[0]
        for step in counters["rows_log"]
    )
    return 100.0 * m["num_ssm_layers"] * least / spent
