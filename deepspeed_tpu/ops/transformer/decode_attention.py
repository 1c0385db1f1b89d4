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
    k_pages: jnp.ndarray,  # [NP, NKV, P, D] — the shared page pool
    v_pages: jnp.ndarray,
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
    page). Compute for table slots past the live length is skipped, but the
    block FETCH is not (pl.when gates the body, not the BlockSpec), so the
    index map clamps ids into [0, NP): tables padded with -1 or sentinel
    ids >= NP read a valid page whose scores are then masked out."""
    B, NH, D = q.shape
    NP, NKV, P, Dk = k_pages.shape
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
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NKV, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, Hg, D), lambda b, g, ki, pt, ln: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, P, D), lambda b, g, ki, pt, ln: (jnp.clip(pt[b, ki], 0, NP - 1), g, 0, 0)),
            pl.BlockSpec((1, 1, P, D), lambda b, g, ki, pt, ln: (jnp.clip(pt[b, ki], 0, NP - 1), g, 0, 0)),
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
    )(jnp.asarray(page_table, jnp.int32), lens, qg, k_pages, v_pages)
    return o.reshape(B, NH, D)


def _ragged_kernel(pt_ref, len_ref, qlen_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s,
                   acc_s, *, scale, page, maxp, Hg):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(ki * page < len_ref[b])
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # [W*Hg, D] — W-major sublanes
        k = k_ref[0, 0].astype(jnp.float32)  # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [W*Hg, page]
        kv_pos = ki * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # sublane i holds query slot w = i // Hg at absolute position
        # start + w, where start = kv_len - q_len (the row's write base)
        q_pos = (len_ref[b] - qlen_ref[b]) + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0
        ) // Hg
        live = (kv_pos <= q_pos) & (kv_pos < len_ref[b])
        s = jnp.where(live, s, NEG_INF)
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


def ragged_paged_attention(
    q: jnp.ndarray,  # [R, W, NH, D] — each row's padded token window
    k_pages: jnp.ndarray,  # [NP, NKV, P, D] — the shared page pool
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [R, MAXP] int32 page ids per row
    kv_lens,  # [R] int32 live kv length INCLUDING this step's tokens
    q_lens,  # [R] int32 real tokens in the row's window (0 = dead row)
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """One ragged kernel for mixed prefill-chunk / decode / verify rows.

    The per-row ``(kv_len, q_len)`` metadata rides in as scalar-prefetch
    arrays (the Ragged Paged Attention design, arXiv 2604.15464): row r's
    window holds ``q_lens[r]`` real tokens written at absolute positions
    ``kv_lens[r] - q_lens[r] ..`` — a decode row is q_len 1, a verify row
    q_len K+1, a prefill chunk q_len C — and the kv grid walks the row's
    page table, skipping pages past ``kv_lens[r]`` entirely, so changing
    the prefill/decode/verify mix only changes ARRAY CONTENTS, never the
    program. Queries ride the sublane dim W-major over the GQA group
    (``[W*Hg, D] x [D, page]`` per block) with a causal in-window mask on
    top of the length mask. Window slots past ``q_lens[r]`` produce
    garbage rows the caller ignores (finite: masked softmax over the live
    prefix); rows with ``kv_lens[r] == 0`` return exact zeros."""
    R, W, NH, D = q.shape
    NP, NKV, P, Dk = k_pages.shape
    assert Dk == D and v_pages.shape == k_pages.shape
    if NH % NKV:
        raise ValueError(f"query heads {NH} not a multiple of kv heads {NKV}")
    maxp = page_table.shape[1]
    scale_f = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if interpret is None:
        interpret = not on_tpu()
    Hg = NH // NKV
    # W-major sublane layout: query slot w of group head h sits at w*Hg + h
    qg = q.reshape(R, W, NKV, Hg, D).transpose(0, 2, 1, 3, 4).reshape(R, NKV, W * Hg, D)
    lens = jnp.broadcast_to(jnp.asarray(kv_lens, jnp.int32), (R,))
    qlens = jnp.broadcast_to(jnp.asarray(q_lens, jnp.int32), (R,))
    kernel = functools.partial(_ragged_kernel, scale=scale_f, page=P, maxp=maxp, Hg=Hg)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, NKV, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, W * Hg, D), lambda b, g, ki, pt, ln, ql: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, P, D), lambda b, g, ki, pt, ln, ql: (jnp.clip(pt[b, ki], 0, NP - 1), g, 0, 0)),
            pl.BlockSpec((1, 1, P, D), lambda b, g, ki, pt, ln, ql: (jnp.clip(pt[b, ki], 0, NP - 1), g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, W * Hg, D), lambda b, g, ki, pt, ln, ql: (b, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((W * Hg, 128), jnp.float32),
            pltpu.VMEM((W * Hg, 128), jnp.float32),
            pltpu.VMEM((W * Hg, D), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, NKV, W * Hg, D), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
        **params,
    )(jnp.asarray(page_table, jnp.int32), lens, qlens, qg, k_pages, v_pages)
    return o.reshape(R, NKV, W, Hg, D).transpose(0, 2, 1, 3, 4).reshape(R, W, NH, D)


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
