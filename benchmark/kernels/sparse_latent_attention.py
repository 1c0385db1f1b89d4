"""Operations and bytes the three parts of a sparse latent layer's attention
(``ops/transformer/sparse_latent_attention.py``) need for one layer of one
serving step, from the step's live rows alone, and the named scopes their
device time is found by.

A row with ``q`` new positions and ``kv`` live keys after the step; query j
sits at position ``kv - q + j`` and may see ``kv - q + j + 1`` keys.

* the INDEX (``sparse_index_scores``): every query scores every key it may
  see, ``index_heads`` (64) products of ``index_head_dim`` (128) and one
  weighted sum: ``2 x 128 x 64 + 2 x 64`` operations a pair. The row reads its
  ``kv`` indexer keys ONCE, 128 numbers each, and its queries.
* the SELECTION (``sparse_select``): no count; its time share is read alone.
* the ATTENTION over chosen entries (``sparse_attend``): query j attends
  ``min(kv - q + j + 1, index_topk)`` entries of ``value + rope`` (512 + 64)
  numbers, ``2 x (576 + 512)`` operations a pair a head. A decode row must
  read its chosen entries once; a chunk row the union of its queries'
  choices, at most its ``kv`` entries: counted as ``min(kv, index_topk + q -
  1)``, the least any choice leaves. An entry counts at 576, not the 640 lanes
  a page stores. A decode row of 128 heads is worth 278 kFLOP an entry for
  1,152 B: 242 FLOP/B, the v5e's ridge, so the larger of the two times is the
  floor.
"""

SCOPES = {"index": "sparse_index_scores", "select": "sparse_select", "attend": "sparse_attend"}
MIXER_SCOPE = "sparse_latent_attention"  # hybrid_moe.SCOPES["sparse_latent"], around the whole mixer


def pairs(q: int, kv: int, most=None) -> int:
    """(query, key) pairs inside the causal mask, ``most`` a query at most."""
    return sum(min(kv - q + j + 1, most or kv) for j in range(q))


def index_ops_and_bytes(rows, index_heads: int, index_dim: int, itemsize: int = 2):
    ops = moved = 0
    for q, kv in rows:
        if q <= 0:
            continue
        ops += (2 * index_dim + 2) * index_heads * pairs(q, kv)
        moved += (kv * index_dim + q * index_heads * index_dim) * itemsize
    return ops, moved


def attend_ops_and_bytes(rows, heads: int, value: int, rope: int, topk: int, itemsize: int = 2):
    ops = moved = 0
    for q, kv in rows:
        if q <= 0:
            continue
        ops += 2 * (2 * value + rope) * pairs(q, kv, topk) * heads
        moved += (min(kv, topk + q - 1) * (value + rope) + q * heads * (2 * value + rope)) * itemsize
    return ops, moved


def min_seconds(ops: int, moved: int, peak) -> float:
    return max(ops / peak["bf16_flops"], moved / peak["hbm_bytes_per_s"])


def scope_time(trace, cell, scope: str) -> float:
    """Seconds of device 0's time in the ops traced inside ``scope``; 0.0 where the trace names none (the parent)."""
    from benchmark import op_scopes

    return op_scopes.scope_self_time(op_scopes.of_cell(cell), trace.devices[0], scope)


def time_share(trace, counters, cell, *scopes: str):
    """Percent of device busy time in the ops traced inside ``scopes``; None without a trace, for a model without
    sparse layers, and where no op names any of them (the parent)."""
    if trace is None or not counters["model"].get("num_sparse_layers"):
        return None
    spent = sum(scope_time(trace, cell, scope) for scope in scopes)
    return 100.0 * spent / trace.devices[0].busy_s() if spent else None


def roofline(trace, counters, cell, scope: str, least_of_step):
    """Percent: ``least_of_step(rows)`` seconds summed over the traced steps
    times the sparse layers, over the device time inside ``scope``. None
    without a trace, a rows log, sparse layers or an op in the scope."""
    m = counters["model"]
    if trace is None or not m.get("num_sparse_layers") or not counters.get("rows_log"):
        return None
    spent = scope_time(trace, cell, scope)
    if not spent:
        return None
    return 100.0 * m["num_sparse_layers"] * sum(least_of_step(step["rows"]) for step in counters["rows_log"]) / spent
