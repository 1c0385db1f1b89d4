"""Bytes and operations the gated short-convolution mixer of
``deepspeed_tpu/inference/hybrid_decode.py`` (the ``conv_mixer`` scope: the
norm, ``[B ; C ; x~] = h W_in``, the gated product, the ``taps``-tap depthwise
convolution over the row's tail and its tokens, the tail's hand-over, ``(C *
v) W_out``) needs for ONE conv layer of one serving step, from the step's live
rows alone.

The layer's weights have to be read ONCE a step, whatever the tile loop does
and however many tokens the step holds: ``W_in`` ``[H, 3 H]``, ``W_out`` ``[H,
H]``, the taps ``[taps, H]`` and the norm's scale ``[H]``, in the served type.
A live row's tail (``taps - 1`` gated products of ``H`` channels, served type)
is read once and written once, however many tokens the row brings. Each of the
row's ``q`` tokens brings its hidden state in and leaves its output (``H``
each, served type) and costs the two projections' arithmetic, ``2 H (3 H + H)``
operations (the products and the convolution's ``2 taps H`` are a thousandth of
that and are not counted). The token buffers between the projections (``u``,
``C``, ``v``) are the program's own choice and are NOT counted: the least a
chip must move is weights, tails and the residual stream. Dead rows need
nothing. Never "all slots": a share computed from these cannot read above what
the chip had to do.
"""


def ops_and_bytes(rows, hidden: int, taps: int = 3, itemsize: int = 2):
    live = [q for q, _kv in rows if q > 0]
    tokens = sum(live)
    moved = (4 * hidden * hidden + (taps + 1) * hidden) * itemsize  # W_in, W_out, the taps, the norm's scale: once a step
    moved += len(live) * 2 * (taps - 1) * hidden * itemsize  # a live row's tail, in and out
    moved += tokens * 2 * hidden * itemsize  # a token's hidden state in, its output out
    return tokens * 2 * hidden * 4 * hidden, moved


def min_seconds(rows, hidden: int, peak, taps: int = 3, itemsize: int = 2):
    """The least time for these rows and which peak bounds it."""
    ops, moved = ops_and_bytes(rows, hidden, taps, itemsize)
    by_ops, by_bytes = ops / peak["bf16_flops"], moved / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes else "memory")
