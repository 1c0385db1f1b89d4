"""Operations and bytes a window layer's latent attention over its ring
(``ops/transformer/sparse_latent_attention.py::ring_latent_attention``) needs
for one layer of one serving step, from the step's live rows alone.

A row with ``q`` new positions and ``kv`` live keys after the step: query j
sees the newest ``window`` (513) entries up to itself, each ``value + rope``
(1,024 + 64) numbers shared by all ``heads`` (64): ``2 x (1,088 + 1,024)``
operations a pair a head. The row must read the entries any of its queries
sees, ``min(kv, window + q - 1)`` of them, once, write its ``q`` new ones,
read q (``heads x 1,088`` a token) and write o (``heads x 1,024``). An entry
counts at 1,088, not the 1,152 lanes a ring page stores, and a decode row at
the 513 it sees, not the ten pages it fetches: both show as lost share.
"""

SCOPE = "ring_latent_attend"
MIXER_SCOPE = "window_latent_attention"  # hybrid_moe.SCOPES["window_latent"], around the whole mixer


def pairs(q: int, kv: int, window: int) -> int:
    return sum(min(kv - q + j + 1, window) for j in range(q))


def ops_and_bytes(rows, heads: int, value: int, rope: int, window: int, itemsize: int = 2):
    ops = moved = 0
    for q, kv in rows:
        if q <= 0:
            continue
        ops += 2 * (2 * value + rope) * pairs(q, kv, window) * heads
        moved += ((min(kv, window + q - 1) + q) * (value + rope) + q * heads * (2 * value + rope)) * itemsize
    return ops, moved


def min_seconds(rows, heads: int, value: int, rope: int, window: int, peak, itemsize: int = 2) -> float:
    ops, moved = ops_and_bytes(rows, heads, value, rope, window, itemsize)
    return max(ops / peak["bf16_flops"], moved / peak["hbm_bytes_per_s"])
