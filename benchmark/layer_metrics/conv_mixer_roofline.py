"""The gated short-convolution mixers' share of their roofline over the traced
steps: the least time for each step's live rows (their ``q_len`` as the driver
logged them) times the conv layers (a layer's weights read ONCE a step, a live
row's tail read and written once, a token's hidden state in and its output
out; bytes and operations in ``benchmark/kernels/short_conv_mixer.py``) over
the device time of the ops inside the ``conv_mixer`` scope. A narrow step is
bound by memory: 33.6 MB of weights a layer for 64 tokens. None for a model
without conv layers, and where the trace has no such scope (the parent)."""

from benchmark import op_scopes
from benchmark.kernels import short_conv_mixer as k


def value(trace, counters, cell):
    m = counters["model"]
    if trace is None or not m.get("num_conv_layers") or not counters.get("rows_log"):
        return None
    spent = op_scopes.scope_self_time(op_scopes.of_cell(cell), trace.devices[0], "conv_mixer")
    if not spent:
        return None
    itemsize = m["conv_tail_bytes_per_row"] // ((m["conv_taps"] - 1) * m["conv_channels"])
    least = sum(k.min_seconds(step["rows"], m["conv_channels"], cell["peak"], m["conv_taps"], itemsize)[0] for step in counters["rows_log"])
    return 100.0 * m["num_conv_layers"] * least / spent
