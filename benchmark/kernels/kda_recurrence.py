"""Operations and bytes the gated delta-rule recurrence of
``deepspeed_tpu/ops/transformer/linear_attention.py`` (the ``kda_recurrence``
scope of ``inference/hybrid_decode.py``: the short convolution, the norms, the
``kda_decode`` kernel or the chunkwise form) needs for ONE linear layer of one
serving step, from the step's live rows alone.

A live row's state ``[heads, d, d]`` float32 has to be read once and written
once, however many tokens the row brings: that is what the chunkwise form is
for, and a decode row's one token is the same count. Each of the row's ``q``
tokens brings its pre-convolution ``q~ k~ v~`` (``3 heads d`` in the served
type), its log decay (``heads d`` float32) and ``b`` (``heads`` float32), and
leaves its output (``heads d``, served type); the convolution's tail
(``K - 1`` inputs of ``3 heads d``) is read and written once a row. A token
costs at least the recurrence's own arithmetic: decay, ``S^T k``, the rank-one
update and ``S^T q``, ``7 d^2`` operations a head. Dead rows need nothing.
Never "all slots": a share computed from these cannot read above what the
chip had to do.
"""


def ops_and_bytes(rows, heads: int, d: int, conv_kernel: int = 4, itemsize: int = 2):
    ops = moved = 0
    for q, _kv in rows:
        if q <= 0:
            continue
        ops += q * 7 * heads * d * d
        moved += 2 * heads * d * d * 4  # the state, in and out
        moved += 2 * (conv_kernel - 1) * 3 * heads * d * itemsize  # the tail, in and out
        moved += q * (3 * heads * d * itemsize + heads * d * 4 + heads * 4 + heads * d * itemsize)
    return ops, moved


def min_seconds(rows, heads: int, d: int, peak, conv_kernel: int = 4, itemsize: int = 2):
    """The least time for these rows and which peak bounds it (the chip's
    matrix peak stands in for the vector units': it is never the one that
    binds)."""
    ops, moved = ops_and_bytes(rows, heads, d, conv_kernel, itemsize)
    by_ops, by_bytes = ops / peak["bf16_flops"], moved / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes else "memory")
