"""The weight stream's share of its roofline over the traced steps: the least
time to read every layer's matrices once a pass (``num_loops`` passes a step)
and the head's once a step at the chip's memory bandwidth
(``benchmark/kernels/dense_weight_stream.py``: bytes from the model's shape
alone, ONE read a pass whatever a wide step's tile loop does, so the share
cannot pass 100%) over the device time of the matrix multiplications that
read them (``weight_stream_time_share`` says which ops). A narrow step is
bound by memory: 8 rows do 8 multiply-adds for every weight they read. None
for a model whose adapter gives no count of a layer's weights, and where no op
names the scopes."""

from benchmark.kernels import dense_weight_stream as k


def value(trace, counters, cell):
    m = counters["model"]
    if trace is None or "intermediate_size" not in m or not counters.get("rows_log"):
        return None
    spent = k.matmul_time(trace, cell)
    if not spent:
        return None
    return 100.0 * k.min_seconds(m, len(counters["rows_log"]), cell["peak"]) / spent
