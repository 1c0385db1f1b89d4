"""Operations and bytes the grouped expert matmuls of
``deepspeed_tpu/moe/grouped_matmul.py`` (the ``moe_experts`` scope of
``moe/routed_ffn.py``) need, from counts alone.

One (token, expert) assignment is one row through its expert's FFN: the gate
and up projections ``[H, I]`` and the down projection ``[I, H]`` (a SwiGLU
expert has those three matrices, a plain one two), ``2 H I`` operations a
matrix. An expert that received at least one row must have its matrices read
once; an expert that received none needs nothing. Each row is read once
(``H`` values) and written once (``H`` values), in the served type. The
counts are the program's own, per step: live assignments and experts hit,
both summed over the layers (``server.stats`` / the ``serve.settle`` span).
Never "all experts": a share computed from these cannot read above what the
chip did.
"""


def ops_and_bytes(assignments: int, experts_hit: int, hidden: int, inter: int, matrices: int = 3, itemsize: int = 2):
    ops = assignments * matrices * 2 * hidden * inter
    moved = (experts_hit * matrices * hidden * inter + assignments * 2 * hidden) * itemsize
    return ops, moved


def min_seconds(assignments: int, experts_hit: int, hidden: int, inter: int, peak, matrices: int = 3, itemsize: int = 2):
    """The least time for these counts and which peak bounds it. Given a
    step's counts summed over its layers this is at most the sum of the
    layers' own least times, so a share over it is never overstated."""
    ops, moved = ops_and_bytes(assignments, experts_hit, hidden, inter, matrices, itemsize)
    by_ops, by_bytes = ops / peak["bf16_flops"], moved / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes else "memory")
