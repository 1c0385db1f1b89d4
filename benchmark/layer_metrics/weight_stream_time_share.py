"""Device time of the matrix multiplications that stream the model's weights
(the projections under ``attention``, the FFN's under ``mlp`` and the head's
under ``head_sample``; the ragged kernel is a custom call and no part of it)
over device busy time. An op is one of them where its name stack ends in
``dot_general`` inside one of the three scopes, or the compiler's category of
it is a convolution (a TPU's name for a matrix multiplication and what it
fused into it). From the ops' name stacks and categories
(``benchmark/kernels/dense_weight_stream.py::matmul_time``). None for a model
whose adapter gives no count of a layer's weights, and where no op names the
scopes (the parent of PR 23)."""

from benchmark.kernels import dense_weight_stream as k


def value(trace, counters, cell):
    if trace is None or "intermediate_size" not in counters["model"]:
        return None
    spent = k.matmul_time(trace, cell)
    return 100.0 * spent / trace.devices[0].busy_s() if spent else None
