"""Pallas ragged KV-cache decode attention (TPU).

Counterpart of the reference's fused ``softmax_context`` decode kernel
(``csrc/transformer/inference/csrc/softmax.cu`` +
``pt_binding.cpp:1935-1974``): one generated token attends over the live
prefix of a preallocated KV cache.

Shape strategy: the single query token's HEADS ride the sublane dim — the
per-block score matmul is [NH, D] x [D, blk] on the MXU — and the kv grid
dimension walks cache blocks with online softmax, skipping blocks past the
row's live length entirely (``pl.when``): HBM reads scale with kv_len, not
cache capacity. Per-batch lengths arrive via scalar prefetch, making the
kernel ragged — each batch row stops at its own length (the paged/ragged
attention the reference approximates with masking).

The serving layer reaches the page-table kernel (``ragged_paged_attention``
below) through ``ops/transformer/paged_attention.py``, which fronts it with
an XLA scatter + gather fallback.

Each ``pallas_call`` carries its entry point's name (``decode_attention``,
``ragged_paged_attention``): a profiler trace finds the kernel by it
(``benchmark/op_scopes.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator import on_tpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *, scale, blk, nk):
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(ki * blk < len_ref[b])
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [NH, D]
        k = k_ref[0].astype(jnp.float32)  # [blk, D]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [NH, blk]
        pos = ki * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < len_ref[b], s, NEG_INF)
        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot(p, v, preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_s[...] / safe_l).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,  # [B, NH, D] — the current token's queries
    k_cache: jnp.ndarray,  # [B, S, NKV, D] — NO GQA pre-expansion needed
    v_cache: jnp.ndarray,
    kv_len,  # [B] int32 live lengths (ragged) or a scalar
    scale: Optional[float] = None,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused single-token attention over each row's live cache prefix.

    Heads grouped per kv head: each grid row (batch, kv-head) computes
    [NH/NKV, D] x [D, blk] — GQA's shared kv rows are read once, not
    repeated NH/NKV times like the dense fallback's jnp.repeat."""
    B, NH, D = q.shape
    S, NKV = k_cache.shape[1], k_cache.shape[2]
    assert k_cache.shape == v_cache.shape == (B, S, NKV, D)
    if NH % NKV:
        raise ValueError(f"query heads {NH} not a multiple of kv heads {NKV}")
    scale_f = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if interpret is None:
        interpret = not on_tpu()
    blk = min(block_k, S)
    if S % blk:
        raise ValueError(f"cache capacity {S} not divisible by block_k {blk}")
    nk = S // blk
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    return _grouped_decode(q, k_cache, v_cache, lens, scale_f, blk, nk, interpret)


def _pages_in_stack(layer, page_table, NP):
    """A layer's page table as indices into the ``L * NP`` pages of all
    layers (the stacked pools seen as one run of pages: a view, no copy),
    sentinel ids (< 0 or >= NP) on the layer's trash page 0: a kernel's index
    map is then a table lookup, with nothing to clamp and no layer to add."""
    return jnp.asarray(layer, jnp.int32) * NP + jnp.clip(
        jnp.asarray(page_table, jnp.int32), 0, NP - 1
    )


def _page_receives(ki, page, kv_len, start):
    """Table slot ``ki`` of a row holds one of the positions
    ``start .. kv_len - 1`` the row writes this step (none where the row is
    dead: ``start == kv_len``)."""
    return (ki * page < kv_len) & ((ki + 1) * page > start) & (start < kv_len)


def _ragged_grid_kernel(pt_ref, len_ref, qlen_ref, x_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref,
                        m_s, l_s, acc_s, *, scale, page, maxp, Hg, W):
    """The kernel for heads that are no whole lanes (``D % 128 != 0``): one
    grid step a (row, kv head, table slot), a written page leaves through the
    out-blocks ``ko_ref`` / ``vo_ref``."""
    b = pl.program_id(0)
    ki = pl.program_id(2)
    kv_len = len_ref[b]
    start = kv_len - qlen_ref[b]  # the row's write base
    receives = _page_receives(ki, page, kv_len, start)
    merged = (ko_ref.at[0, 0], vo_ref.at[0, 0])

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    def attend(k, v):
        q = x_ref[0, 0, : W * Hg].astype(jnp.float32)  # [W*Hg, D] — W-major sublanes
        s = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [W*Hg, page]
        kv_pos = ki * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # sublane i holds query slot w = i // Hg at absolute position start + w
        q_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // Hg
        live = (kv_pos <= q_pos) & (kv_pos < kv_len)
        s = jnp.where(live, s, NEG_INF)
        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot(
            p, v.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when((ki * page < kv_len) & jnp.logical_not(receives))
    def _read_only():
        attend(k_ref[0, 0], v_ref[0, 0])

    @pl.when(receives)
    def _merge_attend_write():
        # the page as the step leaves it: the window's rows where they land,
        # what was read everywhere else; attended from there
        pos = ki * page + jax.lax.broadcasted_iota(jnp.int32, (page, W), 0)
        w = jax.lax.broadcasted_iota(jnp.int32, (page, W), 1)
        sel = pos == start + w  # [page, W] one-hot: window slot w lands on page row p
        hit = (pos[:, :1] >= start) & (pos[:, :1] < kv_len)
        for n, (in_ref, out) in enumerate(zip((k_ref, v_ref), merged)):
            new = x_ref[0, 0, W * (Hg + n) : W * (Hg + n + 1)].astype(out.dtype)  # [W, D]
            if W > 1:
                # one product term a row at most, so exact in the pool's dtype
                new = jax.lax.dot(
                    jnp.where(sel, 1.0, 0.0).astype(new.dtype), new,
                    precision=jax.lax.Precision.HIGHEST if new.dtype == jnp.float32 else None,
                    preferred_element_type=jnp.float32,
                ).astype(new.dtype)
            out[...] = jnp.where(hit, new, in_ref[0, 0])
        attend(merged[0][...], merged[1][...])

    # a fresh out-block is not loaded, so every step writes its own: where
    # nothing is received it sits on the trash page and takes what was
    # read, so the trash page only ever holds finite page contents
    @pl.when(jnp.logical_not(receives))
    def _keep():
        merged[0][...] = k_ref[0, 0]
        merged[1][...] = v_ref[0, 0]

    @pl.when(ki == maxp - 1)
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l == 0, 1.0, l)
        o_ref[0, 0] = (acc_s[...] / safe_l).astype(o_ref.dtype)


def _ragged_by_grid(x, pages, lens, qlens, pools, *, scale, Hg, W, out_dtype, interpret):
    """``_ragged_grid_kernel`` over ``R x NKV x MAXP`` steps; ``pages`` has the
    trash page once more in a last column, for the out-blocks of grid steps
    that write nothing. The path of heads that are no whole lanes and do not
    pack to whole lanes either (``kv_pool.heads_per_group`` is 1: an odd head
    count a shard, a width such as 24 that does not divide 128): a grid step
    for every table slot of every KV head of every row, read or not, and the
    pool copied to row-major around the call. Everything else, granite's and
    GPT-2's heads of 64 included, takes ``_ragged_by_live_pages``."""
    R, NKV, _, D = x.shape
    P = pools[0].shape[2]
    maxp = pages.shape[1] - 1
    kernel = functools.partial(_ragged_grid_kernel, scale=scale, page=P, maxp=maxp, Hg=Hg, W=W)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )

    def row_block(b, g, ki, pt, ln, ql):
        return (b, g, 0, 0)

    def page_read(b, g, ki, pt, ln, ql):
        return (pt[b, ki], g, 0, 0)

    def page_written(b, g, ki, pt, ln, ql):
        receives = _page_receives(ki, P, ln[b], ln[b] - ql[b])
        return (pt[b, jnp.where(receives, ki, maxp)], g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, NKV, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, W * (Hg + 2), D), row_block),
            pl.BlockSpec((1, 1, P, D), page_read),
            pl.BlockSpec((1, 1, P, D), page_read),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, W * Hg, D), row_block),
            pl.BlockSpec((1, 1, P, D), page_written),
            pl.BlockSpec((1, 1, P, D), page_written),
        ],
        scratch_shapes=[
            pltpu.VMEM((W * Hg, 128), jnp.float32),
            pltpu.VMEM((W * Hg, 128), jnp.float32),
            pltpu.VMEM((W * Hg, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, NKV, W * Hg, D), out_dtype)]
        + [jax.ShapeDtypeStruct(pool.shape, pool.dtype) for pool in pools],
        # operands count from the scalars: the pools are the 5th and 6th
        input_output_aliases={4: 1, 5: 2},
        interpret=interpret,
        name="ragged_paged_attention",
        **params,
    )(pages, lens, qlens, x, *pools)


# The ragged kernel's tile sizes, from the shapes alone (read on a v5e at the
# two serving cells' head layouts with ``tools/ragged_kernel_bench.py``;
# ``PERF.md`` section 6, PR 27): bytes of keys (and again of values) a half of
# the double buffer holds, keys a score tile spans at most, float32 scores a
# tile holds (16 vregs), query rows a tile of a wide window holds.
_KV_HALF_BYTES = 1 << 19
_TILE_KEYS = 512
_TILE_SCORES = 16 * 1024
_TILE_ROWS = 128


def _largest_divisor(n: int, limit: int, multiple_of: int = 1) -> int:
    """The largest divisor of ``n`` that is at most ``limit`` and a multiple
    of ``multiple_of``; ``n`` itself where there is none."""
    for d in range(min(n, limit), 0, -1):
        if n % d == 0 and d % multiple_of == 0:
            return d
    return n


def _ragged_tiles(NKV, Hg, W, P, D, maxp, itemsize, pages_per_buffer=None):
    """``(C, CK, TQ, HB)``: pages a half-buffer holds, pages a key tile spans,
    query rows and kv heads a score tile holds. A narrow window (decode,
    verify) is bound by its fetches and takes short halves, which waste least
    on a row's last pages; a wide one is bound by its tiles and takes halves
    of a whole key tile at least."""
    rows = W * Hg
    TQ = rows if rows <= _TILE_ROWS else _largest_divisor(rows, _TILE_ROWS, 8)
    if pages_per_buffer is None:
        pages_per_buffer = max(1, _KV_HALF_BYTES // (NKV * P * D * itemsize))
        if rows >= _TILE_ROWS:
            pages_per_buffer = max(pages_per_buffer, _TILE_KEYS // P)
        pages_per_buffer = min(pages_per_buffer, maxp)
    CK = max(1, min(_TILE_KEYS // P, pages_per_buffer))
    C = pages_per_buffer // CK * CK
    HB = _largest_divisor(NKV, max(1, _TILE_SCORES // ((-(-TQ // 8) * 8) * CK * P)))
    return C, CK, TQ, HB


# The block form (read on a v5e at the two window cells' shapes with
# ``tools/ragged_kernel_bench.py --rows-per-step ... --set _RING_SLOTS=...``;
# ``PERF.md`` section 6, PR 51): rows a grid step attends, slots of a row's
# walk the ring has (the rows whose fetches are in flight and two more), and
# the bytes of keys and values the ring may hold.
_BLOCK_ROWS = 8
_RING_SLOTS = 4
_RING_BYTES = 32 << 20


def _slab(P, itemsize):
    """Rows of a page a DMA moves at least: the pool's sublane tile (16 rows
    of bfloat16), the whole page where that does not divide it."""
    rows = 32 // itemsize
    return P if P % rows else rows


def _ragged_block(NKV, Hg, W, P, D, Dv, CK, itemsize, window, rows_per_step=None):
    """``(RB, PR)``: rows a grid step attends and pages a row's slot holds
    (whole key tiles of ``CK`` pages). One row a step, the walk in halves, where
    nothing bounds a walk (no window), where the rows are wide (a prefill
    chunk's tiles hide their fetches themselves) or where the walks of
    ``_RING_SLOTS`` rows do not fit ``_RING_BYTES``; else ``_BLOCK_ROWS``."""
    if window is None or W * Hg >= _TILE_ROWS:
        return 1, 0
    # the keys a row's queries see between them, window + W - 1, begin anywhere in their first page
    PR = -(-(-(-(window + W - 2) // P) + 1) // CK) * CK
    if rows_per_step is None:
        rows_per_step = _BLOCK_ROWS if _RING_SLOTS * NKV * PR * P * (D + Dv) * itemsize <= _RING_BYTES else 1
    return max(1, rows_per_step), PR


def _merge_new(new_of, bufs, at, run, rows, W, base, start, kv_len):
    """The ``rows`` rows at ``at()`` of the keys' and the values' buffer (run
    ``run`` of as many, counted from position ``base``) take the row's new
    keys and values (``new_of(0)``, ``new_of(1)``: ``[NKV, W, D]`` of the
    row's operand) at the positions ``start .. kv_len - 1`` they land on: a
    one-hot product, exact; every other row is left what it was. Both forms of
    the kernel below merge through this one body (``new_of`` and ``at`` are
    called where the one-row form always read them: its jaxpr is pinned)."""
    pos = lax.add(lax.broadcasted_iota(jnp.int32, (rows, W), 0), lax.add(base, lax.mul(run, rows)))
    w = lax.broadcasted_iota(jnp.int32, (rows, W), 1)
    sel = lax.eq(pos, lax.add(w, start))  # [rows, W] one-hot: window slot w lands on row p
    hit = (pos[:, :1] >= start) & (pos[:, :1] < kv_len)
    for n, buf in enumerate(bufs):
        new = new_of(n).astype(buf.dtype)  # [NKV, W, D]
        if new.shape[-1] != buf.shape[-1]:  # values narrower than keys: their leading lanes
            new = new[..., : buf.shape[-1]]
        if W > 1:
            # one product term a row at most, so exact in the pool's dtype
            new = lax.dot_general(
                jnp.broadcast_to(sel.astype(new.dtype), (new.shape[0], rows, W)), new,
                (((2,), (1,)), ((0,), (0,))),
                precision=lax.Precision.HIGHEST if new.dtype == jnp.float32 else None,
                preferred_element_type=jnp.float32,
            ).astype(buf.dtype)
        buf[at()] = jnp.where(hit, new, buf[at()])


def _key_tile(q, kbuf, vbuf, at, carry, q_pos, base, key0, kv_len, scale, window):
    """One key tile of a query tile's online softmax: ``q`` [HB, TQ, D] against
    the keys and values at ``at`` of the two buffers (the tile's first key at
    position ``base + key0``), under the causal, length and window masks;
    ``carry`` is the float32 ``(m, l, acc)`` so far. Both forms of the kernel
    below attend through this one body, so a row's result does not depend on
    which of them walked it."""
    m, l, acc = carry
    at = (*at, slice(None))
    s = lax.dot_general(
        q, kbuf[at], (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )  # [HB, TQ, TK]
    kv_pos = lax.add(lax.broadcasted_iota(jnp.int32, q_pos.shape, 1), lax.add(base, key0))
    live = lax.bitwise_and(lax.le(kv_pos, q_pos), lax.lt(kv_pos, kv_len))
    if window is not None:
        live = lax.bitwise_and(live, lax.lt(lax.sub(q_pos, kv_pos), window))
    s = jnp.where(live, lax.mul(s, scale), NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = corr * l + jnp.sum(p, axis=2, keepdims=True)
    acc = acc * corr + lax.dot_general(
        p, vbuf[at].astype(jnp.float32),
        (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32,
    )
    return m_new, l, acc


def _ragged_kernel(pt_ref, len_ref, qlen_ref, x_ref, *refs, scale, P, C, CK, TQ, HB, Hg, W, window=None, sinks=False):
    """One row a grid step (``_ragged_block_kernel`` below is the form for
    narrow rows whose walk a window bounds: a block of rows a step). Grid step
    ``g`` attends row ``g - 1`` and starts the fetch of row
    ``g``'s first pages (step 0 only fetches). The row's live pages, all kv
    heads of a page at a time, come by DMA into one half of ``kbuf`` / ``vbuf``
    (``[2, NKV, C * P, D]``: a kv head's keys lie together) while the other
    half is attended. ``k_pool`` / ``v_pool`` are the whole pools, read and
    written in place (the aliased inputs ``_k_in`` / ``_v_in`` are the same
    memory).

    ``window`` (static): a query sees the newest ``window`` keys only, and the
    walk starts at the page of the first key the row's first query sees, not
    at page 0. ``sinks`` (static): one more input, ``[NKV, TQ, 128]`` float32,
    a query row's head's sink in every lane: the running maximum and the
    denominator start from it (one more column of the softmax, value nothing)
    and not from ``-inf`` and 0. A value head may be narrower than a key head
    (``o_ref``'s width): the window's new values ride in ``x_ref``'s leading
    lanes. All three branch in Python, when the kernel is built: with the
    defaults the body traces to the jaxpr it always did.

    The scalar arithmetic is written in ``lax`` primitives: a ``jnp`` function
    or an operator on a traced value is a nested ``jit`` trace, five times the
    price, and this body is traced for every serving program of every process
    (``PERF.md`` section 6, PR 26: what no compilation cache holds)."""
    add, sub, mul, div, lt, gt = lax.add, lax.sub, lax.mul, lax.div, lax.lt, lax.gt
    if sinks:
        sink_ref, *refs = refs
    _k_in, _v_in, o_ref, k_pool, v_pool, kbuf, vbuf, m_s, l_s, acc_s, fetch_sem, write_sem, slot_s = refs
    g = pl.program_id(0)
    R = pl.num_programs(0) - 1
    NKV, rows, Dv = o_ref.shape
    TK = CK * P
    pools = ((k_pool, kbuf), (v_pool, vbuf))  # a pool and the double buffer its pages come into

    def pages_of(row, there):  # where the walk of a row ends: nowhere for a dead one
        walked = lax.bitwise_and(there, gt(qlen_ref[row], 0))
        return lax.select(walked, div(add(len_ref[row], P - 1), P), 0)

    def first_page(row):  # where it starts: the page of the first key the row's first query sees
        return div(lax.max(sub(sub(len_ref[row], qlen_ref[row]), window - 1), 0), P)

    r, nxt = lax.max(sub(g, 1), 0), lax.min(g, R - 1)
    kv_len = len_ref[r]
    start = sub(kv_len, qlen_ref[r])  # the row's write base
    n_pages = pages_of(r, gt(g, 0))
    page0 = 0 if window is None else first_page(r)
    n_buf = div(add(n_pages if window is None else lax.max(sub(n_pages, page0), 0), C - 1), C)
    next_pages = pages_of(nxt, lt(g, R))
    next_first = 0
    if window is not None:
        next_first = first_page(nxt)
        next_pages = sub(next_pages, next_first)  # how many, from there
    n_live = div(add(mul(qlen_ref[r], Hg), TQ - 1), TQ)  # query tiles that hold a real token

    def page_rows(c):
        return pl.ds(pl.multiple_of(mul(c, P), P), P)

    def fetch(row, first, slot, count, wait=False):
        """The copies of ``count`` pages, from table slot ``first`` of ``row``
        on, into half ``slot``: started, or waited for."""

        def page(c, _):
            for i, (pool, buf) in enumerate(pools):
                copy = pltpu.make_async_copy(
                    pool.at[pt_ref[row, add(first, c)]], buf.at[slot, :, page_rows(c), :],
                    fetch_sem.at[slot, i],
                )
                copy.wait() if wait else copy.start()
            return _

        lax.fori_loop(0, count, page, None)

    @pl.when(g == 0)
    def _first_step():
        # what a half holds past a row's live pages is masked, and so must be finite
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_s[0] = 0

    @pl.when(lt(mul(n_live, TQ), rows))
    def _dead_slots():
        o_ref[...] = jnp.zeros_like(o_ref)

    slot0 = slot_s[0]
    n_halves = lax.max(n_buf, 1)  # a step that attends nothing still fetches for the next

    def half(b, _):
        slot = lax.bitwise_and(add(slot0, b), 1)
        first = mul(b, C) if window is None else add(page0, mul(b, C))  # the half's first table slot
        base = mul(first, P)  # and its first key's position
        # the next half's pages, the row's own or else the next row's first, are
        # asked for before this half's are waited for: two halves in flight
        own = lt(add(b, 1), n_buf)
        fetch(
            lax.select(own, r, nxt), lax.select(own, add(first, C), next_first), sub(1, slot),
            lax.min(lax.select(own, sub(n_pages, add(first, C)), next_pages), C),
        )
        fetch(r, first, slot, lax.min(sub(n_pages, first), C), wait=True)

        # pages of this half that receive the row's new positions
        # ``start .. kv_len - 1``: merged here with the window's rows (one-hot,
        # exact), attended from here, written back from here
        c_lo = lax.clamp(0, sub(div(start, P), first), C)
        c_hi = lax.clamp(0, sub(n_pages, first), C)

        def write_back(c, wait=False):
            for i, (pool, buf) in enumerate(pools):
                copy = pltpu.make_async_copy(
                    buf.at[slot, :, page_rows(c), :], pool.at[pt_ref[r, add(first, c)]], write_sem.at[i]
                )
                copy.wait() if wait else copy.start()

        def merge(c, _):
            _merge_new(
                lambda n: x_ref[:, W * (Hg + n) : W * (Hg + n + 1), :], (kbuf, vbuf),
                lambda: (slot, slice(None), page_rows(c), slice(None)), c, P, W, base, start, kv_len,
            )
            write_back(c)
            return _

        lax.fori_loop(c_lo, c_hi, merge, None)

        def head_block(hb, _):
            heads = pl.ds(pl.multiple_of(mul(hb, HB), HB), HB)

            def query_tile(t, _):
                row0 = mul(t, TQ)
                tile = pl.ds(0 if TQ == rows else pl.multiple_of(row0, TQ), TQ)
                q = x_ref[heads, tile, :]  # [HB, TQ, D]: slot w of group head h at row w*Hg + h
                q_pos = add(div(add(lax.broadcasted_iota(jnp.int32, (TQ, TK), 0), row0), Hg), start)
                # keys the tile's last query sees, counted from the half's first
                seen = sub(lax.min(kv_len, add(add(div(add(row0, TQ - 1), Hg), 1), start)), base)

                def key_tile(kt, carry):
                    key0 = mul(kt, TK)
                    keys = pl.ds(pl.multiple_of(key0, TK), TK)
                    return _key_tile(q, kbuf, vbuf, (slot, heads, keys), carry, q_pos, base, key0, kv_len, scale, window)

                first_half = b == 0
                m, l, acc = lax.fori_loop(
                    0, lax.clamp(0, div(add(seen, TK - 1), TK), C // CK), key_tile,
                    (
                        # a tile holds whole groups, so its rows' heads are every tile's
                        jnp.where(first_half, sink_ref[heads, :, :1] if sinks else NEG_INF, m_s[heads, tile, :1]),
                        jnp.where(first_half, 1.0 if sinks else 0.0, l_s[heads, tile, :1]),
                        jnp.where(first_half, 0.0, acc_s[heads, tile, :]),
                    ),
                )
                m_s[heads, tile, :] = jnp.broadcast_to(m, (HB, TQ, 128))
                l_s[heads, tile, :] = jnp.broadcast_to(l, (HB, TQ, 128))
                acc_s[heads, tile, :] = acc

                @pl.when(b == n_buf - 1)
                def _finish():
                    o_ref[heads, tile, :] = (acc / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)

                return _

            if TQ == rows:  # one tile, whatever its height: a static slice
                return query_tile(0, _)
            return lax.fori_loop(0, n_live, query_tile, _)

        lax.fori_loop(0, lax.select(gt(n_pages, 0), NKV // HB, 0), head_block, None)
        # the written pages are on their way since the merge: the half is the
        # next fetch's only once they have left
        lax.fori_loop(c_lo, c_hi, lambda c, _: write_back(c, wait=True), None)
        return _

    lax.fori_loop(0, n_halves, half, None)
    slot_s[0] = lax.bitwise_and(add(slot0, n_halves), 1)  # where the next step finds its first pages


def _ragged_block_kernel(pt_ref, len_ref, qlen_ref, x_ref, *refs, scale, P, RB, NS, PR, CK, HB, SL, Hg, W, window, sinks=False):
    """``_ragged_kernel`` where the window bounds a row's walk to ``PR`` pages
    and the rows are narrow (one query tile): a grid step attends a BLOCK of
    ``RB`` rows, ``x_ref`` and ``o_ref`` hold a block, and a row's whole walk
    lies in a slot of its own, one of a ring of ``NS`` in ``kbuf`` / ``vbuf``
    (``[NS, NKV, PR * P, D]``, from the row's first page on). Before row ``r``
    is attended the fetch of row ``r + NS - 2`` is started, into the slot row
    ``r - 2`` was attended from, whatever blocks the two are in: ``NS - 2``
    rows' copies are in flight all through the call, where ``_ragged_kernel``
    has one half's, and a row pays neither a grid step nor the wait for its
    own write-back.

    Of a walk's first and last page only the ``SL``-row slabs (the pool's
    sublane tile: what a DMA can address) that hold a key the row sees are
    fetched; the pages between come whole. The row's new keys and values are
    merged where they land, a slab at a time, and only those slabs are written
    back, from the row's slot: waited for two rows later, before the slot is
    fetched into again (and at the end of the last step).

    The score tile is ``_key_tile``, the key tiles of ``CK`` pages start at the
    row's first page and the float32 statistics are carried in registers, so a
    row's output is bit for bit what ``_ragged_kernel`` gives it: what a slot
    holds beside the fetched slabs is masked, as a half's dead pages are."""
    add, sub, mul, div, lt, gt = lax.add, lax.sub, lax.mul, lax.div, lax.lt, lax.gt
    if sinks:
        sink_ref, *refs = refs
    _k_in, _v_in, o_ref, k_pool, v_pool, kbuf, vbuf, fetch_sem, write_sem = refs
    g = pl.program_id(0)
    R = len_ref.shape[0]  # whole blocks: dead rows fill the last
    _, NKV, TQ, Dv = o_ref.shape
    TK, SPP, AHEAD = CK * P, P // SL, NS - 2
    pools = ((k_pool, kbuf), (v_pool, vbuf))  # a pool and the ring its pages come into

    def live(row):
        return gt(qlen_ref[row], 0)

    def walk(row):
        """``(kv_len, start, first, fetched, written)`` of a live row: its
        write base, the table slot of the first key its first query sees, and
        two ranges of slabs of its slot, counted from that page's first: those
        that hold a key the row sees and those that hold a position it writes,
        ``start .. kv_len - 1``."""
        kv_len = len_ref[row]
        start = sub(kv_len, qlen_ref[row])
        seen = lax.max(sub(start, window - 1), 0)  # the first key the row's first query sees
        first = div(seen, P)
        end = lax.min(add(div(sub(sub(kv_len, 1), mul(first, P)), SL), 1), PR * SPP)
        at = lambda pos: div(sub(pos, mul(first, P)), SL)
        return kv_len, start, first, (at(seen), end), (at(start), end)

    def copies(row, first, slabs, wait=False, to_pool=False):
        """The copies of the slabs ``slabs`` of the row's slot, from their pages
        or (``to_pool``) to them, started or waited for: those from the pool slab
        by slab up to the first page boundary and from the last on, the pages
        between whole."""
        slot = lax.rem(row, NS)
        lo, hi = slabs
        sem = write_sem if to_pool else fetch_sem

        def copy(c, rows_of_page, rows_of_slot):
            for i, (pool, buf) in enumerate(pools):
                there, here = pool.at[pt_ref[row, add(first, c)], :, rows_of_page, :], buf.at[slot, :, rows_of_slot, :]
                dma = pltpu.make_async_copy(*((here, there) if to_pool else (there, here)), sem.at[slot, i])
                dma.wait() if wait else dma.start()

        def slab(sl, _):
            c = div(sl, SPP)
            copy(c, pl.ds(pl.multiple_of(mul(sub(sl, mul(c, SPP)), SL), SL), SL), pl.ds(pl.multiple_of(mul(sl, SL), SL), SL))
            return _

        def page(c, _):
            copy(c, slice(None), pl.ds(pl.multiple_of(mul(c, P), P), P))
            return _

        if to_pool or SPP == 1:
            lax.fori_loop(lo, hi, slab, None)
            return
        whole_from = lax.min(mul(div(add(lo, SPP - 1), SPP), SPP), hi)
        whole_to = lax.max(mul(div(hi, SPP), SPP), whole_from)
        lax.fori_loop(lo, whole_from, slab, None)
        lax.fori_loop(div(whole_from, SPP), div(whole_to, SPP), page, None)
        lax.fori_loop(whole_to, hi, slab, None)

    def fetch(row):
        _, _, first, fetched, _ = walk(row)
        copies(row, first, fetched)

    def written_slabs_have_left(row):
        _, _, first, _, written = walk(row)
        copies(row, first, written, wait=True, to_pool=True)

    def each(lo, hi, do):  # ``do(row)`` for the live rows of ``lo .. hi - 1``
        lax.fori_loop(lo, hi, lambda row, _: pl.when(live(row))(functools.partial(do, row)), None)

    @pl.when(g == 0)
    def _first_step():
        # what a slot holds beside a row's fetched slabs is masked, and so must be finite
        def clear(slot, _):
            vbuf[slot] = jnp.zeros(vbuf.shape[1:], vbuf.dtype)
            return _

        lax.fori_loop(0, NS, clear, None)
        each(0, min(AHEAD, R), fetch)

    def tiles(slot, j, kv_len, start, base):
        """The row's queries against its slot, a block of kv heads at a time."""
        # keys the row's last query sees, counted from the slot's first
        seen = sub(lax.min(kv_len, add(start, (TQ - 1) // Hg + 1)), base)
        q_pos = add(div(lax.broadcasted_iota(jnp.int32, (TQ, TK), 0), Hg), start)

        def head_block(hb, _):
            heads = pl.ds(pl.multiple_of(mul(hb, HB), HB), HB)
            q = x_ref[j, heads, :TQ, :]  # [HB, TQ, D]: slot w of group head h at row w*Hg + h

            def key_tile(kt, carry):
                key0 = mul(kt, TK)
                keys = pl.ds(pl.multiple_of(key0, TK), TK)
                return _key_tile(q, kbuf, vbuf, (slot, heads, keys), carry, q_pos, base, key0, kv_len, scale, window)

            carry = lax.fori_loop(
                0, lax.clamp(0, div(add(seen, TK - 1), TK), PR // CK), key_tile,
                (
                    # the one tile holds whole groups: its rows' heads are the sinks' block's
                    sink_ref[heads, :, :1] if sinks else jnp.full((HB, TQ, 1), NEG_INF, jnp.float32),
                    jnp.full((HB, TQ, 1), 1.0 if sinks else 0.0, jnp.float32),
                    jnp.zeros((HB, TQ, Dv), jnp.float32),
                ),
            )
            l, acc = carry[1:]
            o_ref[j, heads] = (acc / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)
            return _

        lax.fori_loop(0, NKV // HB, head_block, None)

    def attend(j, _):
        row = add(mul(g, RB), j)
        slot = lax.rem(row, NS)
        # row - 2's slot is the one fetched into next: its written slabs have had a row's arithmetic to leave
        before, ahead = lax.max(sub(row, 2), 0), lax.min(add(row, AHEAD), R - 1)
        pl.when(lax.bitwise_and(gt(row, 1), live(before)))(functools.partial(written_slabs_have_left, before))
        pl.when(lax.bitwise_and(lt(add(row, AHEAD), R), live(ahead)))(functools.partial(fetch, ahead))

        @pl.when(live(row))
        def _live_row():
            kv_len, start, first, fetched, written = walk(row)
            base = mul(first, P)  # the position of the slot's first key
            copies(row, first, fetched, wait=True)

            # the slabs that receive the row's new positions: merged here with the
            # window's rows (one-hot, exact), attended from here, written back from here
            def merge(sl, _):
                rows = pl.ds(pl.multiple_of(mul(sl, SL), SL), SL)
                _merge_new(
                    lambda n: x_ref[j, :, W * (Hg + n) : W * (Hg + n + 1), :], (kbuf, vbuf),
                    lambda: (slot, slice(None), rows, slice(None)), sl, SL, W, base, start, kv_len,
                )
                return _

            lax.fori_loop(*written, merge, None)
            copies(row, first, written, to_pool=True)
            tiles(slot, j, kv_len, start, base)

        @pl.when(lax.eq(qlen_ref[row], 0))
        def _dead_row():
            o_ref[j] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

        return _

    lax.fori_loop(0, RB, attend, None)

    @pl.when(lax.eq(g, pl.num_programs(0) - 1))
    def _last_step():
        each(max(R - 2, 0), R, written_slabs_have_left)


def _ragged_by_live_pages(x, pages, lens, qlens, pools, *, scale, Hg, W, out_dtype, interpret,
                          pages_per_buffer=None, rows_per_step=None, window=None, sinks=None):
    """``_ragged_kernel`` over ``R + 1`` steps or, where ``_ragged_block`` finds
    room for several rows' walks, ``_ragged_block_kernel`` over
    ``ceil(R / RB)``; the pools left where they are. ``sinks`` [NKV * Hg]
    float32 or None."""
    R, NKV, _, D = x.shape
    P, maxp, Dv = pools[0].shape[2], pages.shape[1], pools[1].shape[3]
    itemsize = jnp.dtype(pools[0].dtype).itemsize
    if window is not None and pages_per_buffer is None:
        # a row's walk is the window's pages and the step's own: no half needs more
        maxp = min(maxp, -(-(window - 1) // P) + -(-W // P) + 1)
    C, CK, TQ, HB = _ragged_tiles(NKV, Hg, W, P, D, maxp, itemsize, pages_per_buffer)
    RB, PR = 1, 0
    if pages_per_buffer is None or rows_per_step is not None:
        RB, PR = _ragged_block(NKV, Hg, W, P, D, Dv, CK, itemsize, window, rows_per_step)
    if RB > 1:
        kernel = functools.partial(
            _ragged_block_kernel, scale=scale, P=P, RB=RB, NS=_RING_SLOTS, PR=PR, CK=CK, HB=HB, SL=_slab(P, itemsize),
            Hg=Hg, W=W, window=window, sinks=sinks is not None,
        )
        NB = -(-R // RB)
        if NB * RB != R:  # dead rows fill the last block
            x = jnp.pad(x, ((0, NB * RB - R), (0, 0), (0, 0), (0, 0)))
            pages, lens, qlens = (jnp.pad(a, ((0, NB * RB - R),) + ((0, 0),) * (a.ndim - 1)) for a in (pages, lens, qlens))
        steps, row_dims = NB, (RB, NKV)
        buffers = [(_RING_SLOTS, NKV, PR * P, D), (_RING_SLOTS, NKV, PR * P, Dv)]
        scratch = [
            pltpu.SemaphoreType.DMA((_RING_SLOTS, 2)),  # fetches: a row's slot, keys or values
            pltpu.SemaphoreType.DMA((_RING_SLOTS, 2)),  # write-backs: likewise
        ]
        held_stats = 0
    else:
        kernel = functools.partial(
            _ragged_kernel, scale=scale, P=P, C=C, CK=CK, TQ=TQ, HB=HB, Hg=Hg, W=W
        )
        if window is not None or sinks is not None:
            kernel = functools.partial(kernel, window=window, sinks=sinks is not None)
        steps, row_dims = R + 1, (None, NKV)
        buffers = [(2, NKV, C * P, D), (2, NKV, C * P, Dv)]
        stats = (NKV, W * Hg, 128)
        scratch = [
            pltpu.VMEM(stats, jnp.float32),
            pltpu.VMEM(stats, jnp.float32),
            pltpu.VMEM((NKV, W * Hg, Dv), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),  # fetches: a half, keys or values
            pltpu.SemaphoreType.DMA((2,)),  # write-backs: keys or values
            pltpu.SMEM((1,), jnp.int32),
        ]
        held_stats = 4 * NKV * W * Hg * (2 * 128 + D)  # m, l, acc
    operands, extra_specs = [x], []
    if sinks is not None:
        if TQ % Hg:
            raise ValueError(f"sinks need query tiles of whole groups: {TQ} rows a tile, {Hg} heads a group")
        by_row = jnp.tile(sinks.astype(jnp.float32).reshape(NKV, 1, Hg), (1, TQ // Hg, 1)).reshape(NKV, TQ, 1)
        operands.append(jnp.broadcast_to(by_row, (NKV, TQ, 128)))
        extra_specs.append(pl.BlockSpec((NKV, TQ, 128), lambda g, pt, ln, ql: (0, 0, 0)))
    params = {}
    if not interpret:
        held = (
            2 * int(np.prod(buffers[0])) * itemsize  # the keys' buffer and the values'
            + 2 * RB * NKV * W * (2 * Hg + 2) * D * x.dtype.itemsize  # x and o, twice
            + held_stats
        )
        params["compiler_params"] = pltpu.CompilerParams(
            # a row's first pages are fetched by the step before its own
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=held + (24 << 20),
        )

    def row_block(g, pt, ln, ql):  # step g attends row g - 1 (step 0 only fetches), or block g
        return (g if RB > 1 else lax.max(g - 1, 0), 0, 0, 0)

    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(steps,),
        in_specs=[pl.BlockSpec((*row_dims, W * (Hg + 2), D), row_block), *extra_specs, pool, pool],
        out_specs=[pl.BlockSpec((*row_dims, W * Hg, Dv), row_block), pool, pool],
        scratch_shapes=[pltpu.VMEM(buffers[0], pools[0].dtype), pltpu.VMEM(buffers[1], pools[1].dtype), *scratch],
    )
    o, *new_pools = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((x.shape[0], NKV, W * Hg, Dv), out_dtype)]
        + [jax.ShapeDtypeStruct(pool.shape, pool.dtype) for pool in pools],
        # operands count from the scalars: the pools are the last two inputs
        input_output_aliases={3 + len(operands): 1, 4 + len(operands): 2},
        interpret=interpret,
        name="ragged_paged_attention",
        **params,
    )(pages, lens, qlens, *operands, *pools)
    return (o[:R], *new_pools)


def ragged_paged_attention(
    q: jnp.ndarray,  # [R, W, NH, D] — each row's padded token window
    k_new: jnp.ndarray,  # [R, W, NKV, D] — the window's keys, not yet in the pool
    v_new: jnp.ndarray,
    k_pages: jnp.ndarray,  # [L, NP, NKV, P, D] — every layer's shared page pool
    v_pages: jnp.ndarray,
    layer,  # int32 scalar: the layer whose pool this call writes and reads
    page_table: jnp.ndarray,  # [R, MAXP] int32 page ids per row
    kv_lens,  # [R] int32 live kv length INCLUDING this step's tokens
    q_lens,  # [R] int32 real tokens in the row's window (0 = dead row)
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    pages_per_buffer: Optional[int] = None,
    window: Optional[int] = None,
    sinks: Optional[jnp.ndarray] = None,
    rows_per_step: Optional[int] = None,
):
    """One ragged kernel for mixed prefill-chunk / decode / verify rows that
    writes the step's keys and values into the pool and attends over it.

    The per-row ``(kv_len, q_len)`` metadata rides in as scalar-prefetch
    arrays (the Ragged Paged Attention design, arXiv 2604.15464): row r's
    window holds ``q_lens[r]`` real tokens at absolute positions
    ``kv_lens[r] - q_lens[r] ..`` — a decode row is q_len 1, a verify row
    q_len K+1, a prefill chunk q_len C — so changing the
    prefill/decode/verify mix only changes ARRAY CONTENTS, never the
    program. Queries ride the sublane dim W-major over the GQA group
    (``[W*Hg, D] x [D, keys]`` a kv head) with a causal in-window mask on
    top of the length mask.

    The work follows the row's live pages, not the page table's width: a
    grid step is a row, and its body walks ``ceil(kv_len / P)`` pages only,
    so a dead row and the dead tail of a table cost a few scalar reads (where
    a window bounds the walk of narrow rows, decode or verify, to a few
    pages, a grid step is a BLOCK of rows and a row's pages are fetched
    two rows ahead of it: ``_ragged_block``, ``_ragged_block_kernel``). The
    pools are the whole ``[L, NP, NKV, P, D]`` stacks, left where they are
    (``pl.ANY``), aliased in → out and seen as ``L * NP`` pages (a view), with
    ``layer`` folded into the page table: the kernel is the only operation
    the program ever applies to them, so they stay in one buffer and in the
    default layout through a layer loop that carries them. The body fetches
    all kv heads of several pages at a time by its own DMAs into one half of
    a double buffer in VMEM (about a megabyte of keys and one of values)
    while it attends the other half; a row's first pages are fetched while
    the row before it is attended. The walks over halves, pages, kv heads,
    query tiles and key tiles are rolled loops and the kv heads of a tile one
    batched product, so the body's jaxpr is as long for 2 pages a half as for
    16, for 2 kv heads as for 16.

    A page that holds some of the positions the row writes is merged in the
    half with the window's rows — a one-hot product, exact — attended from
    there and written back by DMA, waited for before the half is fetched
    into again; the window rides behind the queries in one operand. A
    written page belongs to one row (the pool's copy-on-write), so no row
    reads what another writes.

    The dots take q, k and v as they are stored and accumulate in float32
    (a bfloat16 product is exact there); the softmax statistics and the
    accumulator are float32, and so is ``p`` in ``p · v``.

    A head narrower than a lane tile reaches this function at whole lanes:
    the serving pool holds ``128 // D`` such KV heads side by side on a page's
    lanes and the entry (``paged_attention.ragged_paged_attention``) widens
    q, k and v to match, so what arrives here is an attention at heads of
    128. What cannot pack (an odd head count a shard, a width that does not
    divide 128, a caller's pool of its own shape) comes with ``D % 128 != 0``
    and keeps the kernel this one replaced, a grid of ``R x NKV x MAXP`` steps
    with out-blocks on the pools (``_ragged_by_grid``): Mosaic refuses a DMA of
    a page of such a pool, whole or in part ("Slice shape along dimension 3
    must be aligned to tiling (128), but is 64"), and outside the kernel the
    device keeps a pool of half-tile pages with the page index on its lanes,
    so a program that calls this on one transposes the pool whole before the
    call and back after it. No serving cell of the benchmark comes this way.

    A value head may have another width than a key head (``v_new`` and
    ``v_pages`` end in ``Dv``, the result too), and the key pool may be wider
    than ``q`` and ``k_new`` (a head of 192 stored at 256 lanes, so that a page
    is whole lane tiles): they are padded with zeros here, which leaves every
    product what it was. ``window`` (static): a query sees the newest
    ``window`` keys only, itself included, and a row's walk starts at the
    page of the first key its first query sees; the page table then may be a
    ring (table slot i on page ``i % n``) as long as no walk spans more than
    ``n`` pages. ``sinks`` [NH]: a scalar a head that enters the softmax as
    one more column with no value (a row's weights sum to less than one).
    These are static choices made when the kernel is built; neither works
    through the ``_ragged_by_grid`` fallback.

    Returns ``(out [R, W, NH, Dv], k_pages, v_pages)``. Window slots past
    ``q_lens[r]`` are not written and produce garbage rows the caller ignores
    (finite: masked softmax over the live prefix, or zeros); rows with
    ``q_lens[r] == 0`` return exact zeros. ``pages_per_buffer`` overrides the
    size of a half and ``rows_per_step`` the rows of a block, 1 for the form
    of one row a grid step (tests, ``tools/ragged_kernel_bench.py``)."""
    R, W, NH, Dq = q.shape
    L, NP, NKV, P, D = k_pages.shape
    Dv = v_pages.shape[-1]
    assert Dq <= D and v_pages.shape == k_pages.shape[:-1] + (Dv,) and v_pages.dtype == k_pages.dtype
    assert k_new.shape == (R, W, NKV, Dq) and v_new.shape == (R, W, NKV, Dv) and Dv <= D
    if NH % NKV:
        raise ValueError(f"query heads {NH} not a multiple of kv heads {NKV}")
    maxp = page_table.shape[1]
    scale_f = float(scale) if scale is not None else 1.0 / float(np.sqrt(Dq))
    if interpret is None:
        interpret = not on_tpu()
    Hg = NH // NKV
    plain = Dq == D == Dv and window is None and sinks is None
    if not plain:  # everything in the one operand at the key pool's width
        lanes = lambda a: jnp.pad(a, ((0, 0),) * 3 + ((0, D - a.shape[-1]),))
        q, k_new, v_new = lanes(q), lanes(k_new), lanes(v_new)
    # one operand a row: the queries W-major (slot w of group head h at
    # sublane w*Hg + h), then the window's keys, then its values — rounded
    # to the pool's dtype first, in a dtype that holds both exactly
    xdtype = jnp.promote_types(q.dtype, k_pages.dtype)
    x = jnp.concatenate(
        [q.reshape(R, W, NKV, Hg, D).transpose(0, 2, 1, 3, 4).reshape(R, NKV, W * Hg, D)]
        + [new.astype(k_pages.dtype).transpose(0, 2, 1, 3) for new in (k_new, v_new)],
        axis=2, dtype=xdtype,
    )
    lens = jnp.broadcast_to(jnp.asarray(kv_lens, jnp.int32), (R,))
    qlens = jnp.broadcast_to(jnp.asarray(q_lens, jnp.int32), (R,))
    pools = [k_pages.reshape(L * NP, NKV, P, D), v_pages.reshape(L * NP, NKV, P, Dv)]
    shared = dict(scale=scale_f, Hg=Hg, W=W, out_dtype=q.dtype, interpret=interpret)
    if D % 128 == 0 and Dv % 128 == 0:  # a page of all kv heads is a slab a DMA can address
        if not plain:
            shared.update(window=window, sinks=sinks)
        o, new_k, new_v = _ragged_by_live_pages(
            x, _pages_in_stack(layer, page_table, NP), lens, qlens, pools,
            pages_per_buffer=pages_per_buffer, rows_per_step=rows_per_step, **shared,
        )
    elif not plain:
        raise NotImplementedError(
            f"a window, sinks or a value width of its own need pages of whole lane tiles: keys {D}, values {Dv}"
        )
    else:
        trash_last = jnp.pad(page_table, ((0, 0), (0, 1)), constant_values=-1)
        o, new_k, new_v = _ragged_by_grid(
            x, _pages_in_stack(layer, trash_last, NP), lens, qlens, pools, **shared
        )
    o = o.reshape(R, NKV, W, Hg, Dv).transpose(0, 2, 1, 3, 4).reshape(R, W, NH, Dv)
    return o, new_k.reshape(k_pages.shape), new_v.reshape(v_pages.shape)


def _grouped_decode(q, k_cache, v_cache, lens, scale_f, blk, nk, interpret):
    """Group heads by shared kv rows. With the cache stored per kv head and
    queries pre-grouped [B, G, Hg, D] (Hg = heads per kv head), each grid
    row (b, g) computes [Hg, D] x [D, blk] — for MHA Hg=1 folds into BN
    rows; for GQA the group's heads batch into the sublane dim."""
    B, NH, D = q.shape
    S = k_cache.shape[1]
    NKV = k_cache.shape[2]
    Hg = NH // NKV
    # q: [B, NKV, Hg, D] rows; kv: [B, NKV, S, D]
    qg = q.reshape(B, NKV, Hg, D).reshape(B * NKV, Hg, D)
    kg = k_cache.transpose(0, 2, 1, 3).reshape(B * NKV, S, D)
    vg = v_cache.transpose(0, 2, 1, 3).reshape(B * NKV, S, D)
    lens_g = jnp.repeat(lens, NKV)
    kernel = functools.partial(_decode_kernel, scale=scale_f, blk=blk, nk=nk)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * NKV, nk),
        in_specs=[
            pl.BlockSpec((1, Hg, D), lambda b, ki, lens_ref: (b, 0, 0)),
            pl.BlockSpec((1, blk, D), lambda b, ki, lens_ref: (b, ki, 0)),
            pl.BlockSpec((1, blk, D), lambda b, ki, lens_ref: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hg, D), lambda b, ki, lens_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hg, 128), jnp.float32),
            pltpu.VMEM((Hg, 128), jnp.float32),
            pltpu.VMEM((Hg, D), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * NKV, Hg, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
        **params,
    )(lens_g, qg, kg, vg)
    return o.reshape(B, NKV, Hg, D).reshape(B, NH, D)
