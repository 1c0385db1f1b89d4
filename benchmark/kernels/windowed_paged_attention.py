"""Operations and bytes the ragged paged-attention kernel of
``ops/transformer/decode_attention.py`` needs for one layer of one serving
step when a layer's queries see a sliding window and its key and value heads
have widths of their own, from the step's live rows alone.

A row with ``q`` new positions and ``kv`` live keys after the step: query j
sits at position ``kv - q + j`` and sees the keys up to itself, ``kv - q + j +
1`` of them, in a window layer the newest ``window`` of those at most. Each
pair inside the mask costs ``2 dk`` operations for S and ``2 dv`` for P V, for
each of the ``heads`` query heads; pairs outside the mask cost nothing. The
row must read the keys any of its queries sees, ``min(kv, window + q - 1)``
of them (all ``kv`` in a full layer, ``window`` None), of ``kv_heads`` heads,
``dk`` a key and ``dv`` a value, once; read q and write o. ``dk`` is what the
mathematics needs (192), not what a page stores (256 lanes): the padding shows
as lost share. Dead rows and slots past ``q`` need nothing. A sink is a scalar
a head and is not counted.
"""

KERNEL = "ragged_paged_attention"  # the pallas_call's name=, in the op's name stack


def pairs(q: int, kv: int, window=None) -> int:
    """(query, key) pairs inside the mask."""
    return sum(min(kv - q + j + 1, window or kv) for j in range(q))


def keys_read(q: int, kv: int, window=None) -> int:
    return kv if window is None else min(kv, window + q - 1)


def ops_and_bytes(rows, heads: int, kv_heads: int, dk: int, dv: int, window=None, itemsize: int = 2):
    ops = moved = 0
    for q, kv in rows:
        if q <= 0:
            continue
        ops += 2 * (dk + dv) * pairs(q, kv, window) * heads
        moved += (keys_read(q, kv, window) * kv_heads * (dk + dv) + q * heads * (dk + dv)) * itemsize
    return ops, moved


def min_seconds(rows, heads: int, kv_heads: int, dk: int, dv: int, peak, window=None, itemsize: int = 2):
    ops, moved = ops_and_bytes(rows, heads, kv_heads, dk, dv, window, itemsize)
    by_ops, by_bytes = ops / peak["bf16_flops"], moved / peak["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), ("compute" if by_ops >= by_bytes else "memory")


def scoped_kernel_time(names, dev, scope: str):
    """Seconds of ``dev``'s device time in this kernel's calls traced inside
    ``scope``, and how many calls; (0.0, 0) where the trace names none."""
    from benchmark import op_scopes

    events = [
        ev for ev in op_scopes.kernel_events(names, dev, [KERNEL])[KERNEL]
        if op_scopes.in_scope(names.stack(dev.ordinal, ev.name), scope)
    ]
    return sum(ev.duration for ev in events), len(events)


def roofline(trace, counters, cell, scope: str, layers: int, kv_heads: int, window):
    """Percent: the least time for the traced steps' rows times the ``layers``
    of one kind over the kernel's own device time inside ``scope``. None
    without a trace, a rows log, such layers or such calls."""
    from benchmark import op_scopes

    m = counters["model"]
    if trace is None or not layers or not counters.get("rows_log"):
        return None
    spent, _ = scoped_kernel_time(op_scopes.of_cell(cell), trace.devices[0], scope)
    if not spent:
        return None
    least = sum(
        min_seconds(step["rows"], m["num_heads"], kv_heads, m["qk_head_dim"], m["v_head_dim"], cell["peak"], window)[0]
        for step in counters["rows_log"]
    )
    return 100.0 * layers * least / spent
