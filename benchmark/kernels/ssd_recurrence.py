"""Operations and bytes the state-space recurrence of
``deepspeed_tpu/ops/transformer/state_space.py`` (the ``ssd_recurrence`` scope
of ``inference/hybrid_decode.py``: the convolution with its bias and SiLU, the
``ssd_decode`` kernel or the chunk form) needs for ONE Mamba-2 layer of one
serving step, from the step's live rows alone.

A live row's state ``[heads, head_dim, state]`` float32 has to be read once
and written once, however many tokens the row brings: that is what the chunk
form is for, and a decode row's one token is the same count. The convolution's
tail (``K - 1`` inputs of ``channels = heads head_dim + 2 state``, served type)
is read and written once a row. Each of the row's ``q`` tokens brings its
pre-convolution ``[x ; B ; C]`` (``channels``, served type) and ``dt`` (``heads``
float32), and leaves its output ``y`` (``heads head_dim``, served type). A token
costs at least the recurrence's own arithmetic: the decay, the rank-one update
and the read-out ``S C``, ``5 head_dim state`` operations a head. The gate
``z``, the gated norm and the two projections lie outside the scope and are
not counted. Dead rows need nothing. Never "all slots": a share computed from
these cannot read above what the chip had to do.
"""


def ops_and_bytes(rows, heads: int, head_dim: int, state: int, channels: int, conv_kernel: int = 4, itemsize: int = 2):
    ops = moved = 0
    for q, _kv in rows:
        if q <= 0:
            continue
        ops += q * 5 * heads * head_dim * state
        moved += 2 * heads * head_dim * state * 4  # the state, in and out
        moved += 2 * (conv_kernel - 1) * channels * itemsize  # the tail, in and out
        moved += q * (channels * itemsize + heads * 4 + heads * head_dim * itemsize)
    return ops, moved


def min_seconds(rows, heads: int, head_dim: int, state: int, channels: int, peak, conv_kernel: int = 4, itemsize: int = 2):
    """The least time for these rows and which peak bounds it (the chip's
    matrix peak stands in for the vector units': it is never the one that
    binds)."""
    ops, moved = ops_and_bytes(rows, heads, head_dim, state, channels, conv_kernel, itemsize)
    by_ops, by_bytes = ops / peak["bf16_flops"], moved / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes else "memory")
