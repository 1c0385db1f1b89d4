"""Operations and bytes the ragged paged-attention kernel of
``ops/transformer/decode_attention.py`` needs for one layer of one serving
step, from the step's live rows alone, and how its events are named.

A row with ``q`` new positions and ``kv`` live keys after the step attends
causally inside its window: query j sees ``kv - q + j + 1`` keys, so the row
has ``q kv - q (q - 1) / 2`` pairs, each ``2 d`` operations for S and again
for P V, for each of the ``heads`` query heads. It must read ``kv`` keys and
values of ``kv_heads`` heads once, read q and write o. Dead rows and slots
past ``q`` need nothing.
"""

# On this runtime (PR 22 trace) the event's name is the custom call's HLO text;
# the kernel is told by its three scalar-prefetch operands (page table, kv
# lengths, q lengths: s32) in front of q and the two page pools.
_S32 = r"s32\[[\d,]+\] %[\w.-]+, "
EVENTS = {"ragged": r"custom-call\(" + _S32 * 3 + r'.*custom_call_target="tpu_custom_call"'}


def calls_per_step(num_layers: int):
    """Kernel calls in one serving step: one a layer."""
    return {"ragged": num_layers}


def ops_and_bytes(rows, heads: int, kv_heads: int, d: int, itemsize: int = 2):
    ops = moved = 0
    for q, kv in rows:
        pairs = q * kv - q * (q - 1) // 2
        ops += 4 * d * pairs * heads
        moved += (2 * kv * kv_heads * d + 2 * q * heads * d) * itemsize
    return ops, moved


def min_seconds(rows, heads: int, kv_heads: int, d: int, peak, itemsize: int = 2):
    ops, moved = ops_and_bytes(rows, heads, kv_heads, d, itemsize)
    by_ops, by_bytes = ops / peak["bf16_flops"], moved / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes else "memory")
