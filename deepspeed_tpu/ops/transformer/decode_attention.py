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

The serving layer reaches the page-table variant (``paged_decode_attention``
below) through ``ops/transformer/paged_attention.py``, which fronts it with
an XLA gather fallback and the chunk-prefill attention.

Each ``pallas_call`` carries its entry point's name (``decode_attention``,
``paged_decode_attention``, ``ragged_paged_attention``): a profiler trace
finds the kernel by it (``benchmark/op_scopes.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
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


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *, scale, page, maxp):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(ki * page < len_ref[b])
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [Hg, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        pos = ki * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
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

    @pl.when(ki == maxp - 1)
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l == 0, 1.0, l)
        o_ref[0, 0] = (acc_s[...] / safe_l).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,  # [B, NH, D]
    k_pages: jnp.ndarray,  # [L, NP, NKV, P, D] — every layer's shared page pool
    v_pages: jnp.ndarray,
    layer,  # int32 scalar: the layer whose pool is read
    page_table: jnp.ndarray,  # [B, MAXP] int32 page ids per sequence
    kv_len,  # [B] int32 live lengths
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Paged (block-table) decode attention — the vLLM-style serving layout
    the reference approximates with contiguous per-sequence workspaces: each
    sequence's cache is a list of pages in a shared pool, so prefixes can be
    shared and memory allocates page-granular. The kernel's kv grid walks
    the page table via scalar prefetch (k/v BlockSpecs jump straight to the
    page; the stacked pools are seen as ``L * NP`` pages and ``layer`` is
    folded into the table, so no slice of the stack is ever made). Compute
    for table slots past the live length is skipped, but the
    block FETCH is not (pl.when gates the body, not the BlockSpec), so the
    table's ids are clamped into [0, NP): tables padded with -1 or sentinel
    ids >= NP read a valid page whose scores are then masked out."""
    B, NH, D = q.shape
    L, NP, NKV, P, Dk = k_pages.shape
    assert Dk == D and v_pages.shape == k_pages.shape
    if NH % NKV:
        raise ValueError(f"query heads {NH} not a multiple of kv heads {NKV}")
    maxp = page_table.shape[1]
    scale_f = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if interpret is None:
        interpret = not on_tpu()
    Hg = NH // NKV
    qg = q.reshape(B, NKV, Hg, D)
    lens = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (B,))
    kernel = functools.partial(_paged_kernel, scale=scale_f, page=P, maxp=maxp)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    pages = _pages_in_stack(layer, page_table, NP)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NKV, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, Hg, D), lambda b, g, ki, pt, ln: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, P, D), lambda b, g, ki, pt, ln: (pt[b, ki], g, 0, 0)),
            pl.BlockSpec((1, 1, P, D), lambda b, g, ki, pt, ln: (pt[b, ki], g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Hg, D), lambda b, g, ki, pt, ln: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hg, 128), jnp.float32),
            pltpu.VMEM((Hg, 128), jnp.float32),
            pltpu.VMEM((Hg, D), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NKV, Hg, D), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
        **params,
    )(pages, lens, qg, k_pages.reshape(L * NP, NKV, P, D), v_pages.reshape(L * NP, NKV, P, D))
    return o.reshape(B, NH, D)


def _page_receives(ki, page, kv_len, start):
    """Table slot ``ki`` of a row holds one of the positions
    ``start .. kv_len - 1`` the row writes this step (none where the row is
    dead: ``start == kv_len``)."""
    return (ki * page < kv_len) & ((ki + 1) * page > start) & (start < kv_len)


def _ragged_kernel(pt_ref, len_ref, qlen_ref, x_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref,
                   m_s, l_s, acc_s, *page_bufs, scale, page, maxp, Hg, W):
    """``page_bufs``: ``(kbuf, vbuf, sem)`` where a written page leaves by DMA
    (``ko_ref`` / ``vo_ref`` are then the whole pools, in place), nothing
    where it leaves through the out-blocks ``ko_ref`` / ``vo_ref``."""
    b = pl.program_id(0)
    g = pl.program_id(1)
    ki = pl.program_id(2)
    kv_len = len_ref[b]
    start = kv_len - qlen_ref[b]  # the row's write base
    receives = _page_receives(ki, page, kv_len, start)
    by_dma = bool(page_bufs)
    merged = page_bufs[:2] if by_dma else (ko_ref.at[0, 0], vo_ref.at[0, 0])

    def write_back(slot):
        """The two copies of the merged pages to the page of table slot
        ``slot``, to start and later to wait for."""
        return [
            pltpu.make_async_copy(buf, pool.at[pt_ref[b, slot], g], page_bufs[2].at[i])
            for i, (buf, pool) in enumerate(zip(merged, (ko_ref, vo_ref)))
        ]

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
        # what was read everywhere else; attended from there and written back
        if by_dma:

            @pl.when(ki * page > start)
            def _buffers_free():  # the row's page before this one is on its way
                for copy in write_back(ki - 1):
                    copy.wait()

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
        if by_dma:
            for copy in write_back(ki):
                copy.start()
        attend(merged[0][...], merged[1][...])

    if not by_dma:
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
        if by_dma:

            @pl.when(start < kv_len)
            def _written():  # the buffers are the next (row, kv head)'s from here
                for copy in write_back((kv_len - 1) // page):
                    copy.wait()


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
):
    """One ragged kernel for mixed prefill-chunk / decode / verify rows that
    writes the step's keys and values into the pool and attends over it.

    The per-row ``(kv_len, q_len)`` metadata rides in as scalar-prefetch
    arrays (the Ragged Paged Attention design, arXiv 2604.15464): row r's
    window holds ``q_lens[r]`` real tokens at absolute positions
    ``kv_lens[r] - q_lens[r] ..`` — a decode row is q_len 1, a verify row
    q_len K+1, a prefill chunk q_len C — and the kv grid walks the row's
    page table, skipping pages past ``kv_lens[r]`` entirely, so changing
    the prefill/decode/verify mix only changes ARRAY CONTENTS, never the
    program. Queries ride the sublane dim W-major over the GQA group
    (``[W*Hg, D] x [D, page]`` per block) with a causal in-window mask on
    top of the length mask.

    The pools are the whole ``[L, NP, NKV, P, D]`` stacks, aliased in → out
    and seen as ``L * NP`` pages (a view), with ``layer`` folded into the page
    table: the kernel is the only operation the program ever applies to
    them, so they stay in one buffer and in the default layout through a
    layer loop that carries them. A page that holds some of the positions
    the row writes (``_page_receives``) is merged with the window's rows as
    it is read — a one-hot product, exact — attended from there and written
    back; the window rides behind the queries in one operand. A written page
    belongs to one row (the pool's copy-on-write) and is visited once a kv
    head, so no grid step reads what another writes.

    How a written page leaves: where a ``[P, D]`` page is whole lanes
    (``D % 128 == 0``) it is merged in a VMEM buffer and copied to the pool
    by a DMA that is waited for when the row's next page needs the buffer
    or its kv head is done, and a grid step that writes nothing moves and
    computes what it did when the write was XLA's (measured on a v5e at the
    Mistral cell's shapes, the kernel alone: +2-3% a call; through
    out-blocks +28%). Mosaic
    refuses that DMA for a narrower head (the pool's last dimension is
    padded to 128 lanes and a page is no aligned slice of it), so there the
    pools have out-blocks: on the page where it receives, on the trash page
    0 everywhere else.

    Returns ``(out [R, W, NH, D], k_pages, v_pages)``. Window slots past
    ``q_lens[r]`` are not written and produce garbage rows the caller ignores
    (finite: masked softmax over the live prefix); rows with
    ``kv_lens[r] == 0`` return exact zeros."""
    R, W, NH, D = q.shape
    L, NP, NKV, P, Dk = k_pages.shape
    assert Dk == D and v_pages.shape == k_pages.shape and v_pages.dtype == k_pages.dtype
    assert k_new.shape == v_new.shape == (R, W, NKV, D)
    if NH % NKV:
        raise ValueError(f"query heads {NH} not a multiple of kv heads {NKV}")
    maxp = page_table.shape[1]
    scale_f = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if interpret is None:
        interpret = not on_tpu()
    Hg = NH // NKV
    # one operand a (row, kv head): the queries W-major (slot w of group head h
    # at sublane w*Hg + h), then the window's keys, then its values — rounded
    # to the pool's dtype first, in a dtype that holds both exactly
    xdtype = jnp.promote_types(q.dtype, k_pages.dtype)
    x = jnp.concatenate(
        [q.reshape(R, W, NKV, Hg, D).transpose(0, 2, 1, 3, 4).reshape(R, NKV, W * Hg, D)]
        + [new.astype(k_pages.dtype).transpose(0, 2, 1, 3) for new in (k_new, v_new)],
        axis=2, dtype=xdtype,
    )
    lens = jnp.broadcast_to(jnp.asarray(kv_lens, jnp.int32), (R,))
    qlens = jnp.broadcast_to(jnp.asarray(q_lens, jnp.int32), (R,))
    # the trash page once more in a last column, for the out-blocks of grid
    # steps that write nothing
    pages = _pages_in_stack(layer, jnp.pad(page_table, ((0, 0), (0, 1)), constant_values=-1), NP)
    kernel = functools.partial(_ragged_kernel, scale=scale_f, page=P, maxp=maxp, Hg=Hg, W=W)
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

    if D % 128 == 0:  # a page is a slab a DMA can address
        pool_out = pl.BlockSpec(memory_space=pl.ANY)
        page_bufs = [
            pltpu.VMEM((P, D), k_pages.dtype),
            pltpu.VMEM((P, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    else:
        pool_out, page_bufs = pl.BlockSpec((1, 1, P, D), page_written), []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, NKV, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, W * (Hg + 2), D), row_block),
            pl.BlockSpec((1, 1, P, D), page_read),
            pl.BlockSpec((1, 1, P, D), page_read),
        ],
        out_specs=[pl.BlockSpec((1, 1, W * Hg, D), row_block), pool_out, pool_out],
        scratch_shapes=[
            pltpu.VMEM((W * Hg, 128), jnp.float32),
            pltpu.VMEM((W * Hg, 128), jnp.float32),
            pltpu.VMEM((W * Hg, D), jnp.float32),
            *page_bufs,
        ],
    )
    o, new_k, new_v = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((R, NKV, W * Hg, D), q.dtype),
            jax.ShapeDtypeStruct((L * NP, NKV, P, D), k_pages.dtype),
            jax.ShapeDtypeStruct((L * NP, NKV, P, D), v_pages.dtype),
        ],
        # operands count from the scalars: the pools are the 5th and 6th
        input_output_aliases={4: 1, 5: 2},
        interpret=interpret,
        name="ragged_paged_attention",
        **params,
    )(
        pages, lens, qlens, x,
        k_pages.reshape(L * NP, NKV, P, D), v_pages.reshape(L * NP, NKV, P, D),
    )
    o = o.reshape(R, NKV, W, Hg, D).transpose(0, 2, 1, 3, 4).reshape(R, W, NH, D)
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
