"""Bytes a dense decoder's serving step has to read of its WEIGHTS, from the
model's shape alone (an adapter's ``shape``): the matrices of every layer
once a PASS over the stack (``num_loops`` passes: a looped model reads the same
stacks again in every pass, because nothing of a pass's 4.9 GB survives on the
chip to the next) and the head's matrix once a step.

A layer's matrices: q, k, v and output projections ``H x (NH + 2 NKV) D`` and
``NH D x H``, and the FFN's ``H x I`` two or three times (a gated FFN has a gate
beside up and down). Norm scales, biases and the exit gate are thousands of
bytes and left out; the embedding's gather reads a row a token and is left
out. The count is ONE read a pass whatever a wide step's tile loop does (a
step of ``n`` token tiles streams a layer's matrices ``n`` times; that is the
program's choice, not the algorithm's need), so a share computed from it
cannot read above what the chip had to do.
"""


def layer_weight_bytes(m, itemsize: int = 2) -> int:
    """The matrices of ONE layer."""
    H, D = m["hidden_size"], m["head_dim"]
    attn = H * (m["num_heads"] + 2 * m["num_kv_heads"]) * D + m["num_heads"] * D * H
    ffn = (3 if m["swiglu"] else 2) * H * m["intermediate_size"]
    return (attn + ffn) * itemsize


def step_weight_bytes(m, itemsize: int = 2) -> int:
    """One serving step: every layer's matrices once a pass, the head once."""
    passes, layers = m.get("num_loops", 1), m.get("weight_layers", m["num_layers"])
    return passes * layers * layer_weight_bytes(m, itemsize) + m["hidden_size"] * m["vocab_size"] * itemsize


def min_seconds(m, steps: int, peak, itemsize: int = 2) -> float:
    """The least time ``steps`` steps need to read their weights at the chip's memory bandwidth."""
    return steps * step_weight_bytes(m, itemsize) / peak["hbm_bytes_per_s"]


SCOPES = ("attention", "mlp", "head_sample")


def matmul_time(trace, cell) -> float:
    """Seconds of device 0's time in the matrix multiplications inside the
    serving step's three scopes: leaf ops whose name stack ends in
    ``dot_general`` or whose category is a convolution (a fusion is named and
    categorised by the multiplication it holds). 0.0 where the trace names none."""
    from benchmark import op_scopes

    names = op_scopes.of_cell(cell)
    dev = trace.devices[0]
    memo = {}
    total = 0.0
    for ev in dev.leaves:
        is_matmul = memo.get(ev.name)
        if is_matmul is None:
            stats = names.stats(dev.ordinal, ev.name)
            stack = str(stats.get(op_scopes.NAME_STACK, ""))
            parts = op_scopes.components(stack)
            is_matmul = memo[ev.name] = bool(parts) and any(op_scopes.in_scope(stack, s) for s in SCOPES) and (
                parts[-1] == "dot_general" or str(stats.get("hlo_category", "")).startswith("convolution")
            )
        if is_matmul:
            total += ev.duration
    return total
