"""Operations and bytes the latent paged-attention kernel of
``ops/transformer/latent_attention.py`` needs for one layer of one serving
step, from the step's live rows alone, and how its calls are found.

A token's entry is ``[c_kv ; k_rope]``: ``value`` (512) + ``rope`` (64) = 576
numbers, and all ``heads`` (20) query heads share it. A row with ``q`` new
positions and ``kv`` live entries after the step: query j sits at position
``kv - q + j`` and sees the entries up to itself, ``kv - q + j + 1`` of them.
Each (query, entry) pair costs ``2 x 576`` operations for the score and ``2 x
512`` for P V, for each head. The row must read its ``kv`` entries ONCE (576
numbers each: the value is the entry's own leading lanes, not a second
array), write its ``q`` new entries, read q (``heads x 576`` a token) and
write o (``heads x 512``). An entry counts at 576, what the mathematics needs,
not the 640 lanes a page stores: the padding shows as lost share. Dead rows
and slots past ``q`` need nothing.
"""

KERNEL = "latent_paged_attention"  # the pallas_call's name=, in the op's name stack
SCOPE = "latent_attention"  # hybrid_moe.SCOPES["latent"], around the whole mixer


def pairs(q: int, kv: int) -> int:
    """(query, entry) pairs inside the causal mask."""
    return q * kv - q * (q - 1) // 2


def ops_and_bytes(rows, heads: int, value: int, rope: int, itemsize: int = 2):
    ops = moved = 0
    for q, kv in rows:
        if q <= 0:
            continue
        ops += 2 * (2 * value + rope) * pairs(q, kv) * heads
        moved += ((kv + q) * (value + rope) + q * heads * (2 * value + rope)) * itemsize
    return ops, moved


def min_seconds(rows, heads: int, value: int, rope: int, peak, itemsize: int = 2):
    ops, moved = ops_and_bytes(rows, heads, value, rope, itemsize)
    by_ops, by_bytes = ops / peak["bf16_flops"], moved / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes else "memory")


def scope_and_kernel_time(trace, cell):
    """Seconds of device 0's time in the ops traced inside the latent mixer's
    scope, and of those the seconds in this kernel's own calls; (0.0, 0.0)
    where the trace names neither (a model without latent layers, the parent)."""
    from benchmark import op_scopes

    names, dev = op_scopes.of_cell(cell), trace.devices[0]
    kernel = sum(
        ev.duration for ev in op_scopes.kernel_events(names, dev, [KERNEL])[KERNEL]
        if op_scopes.in_scope(names.stack(dev.ordinal, ev.name), SCOPE)
    )
    return op_scopes.scope_self_time(names, dev, SCOPE), kernel
