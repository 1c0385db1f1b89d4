"""Pallas block-sparse attention (TPU).

The kernel the reference implements in 2,285 LoC of Triton
(``deepspeed/ops/sparse_attention/trsrc/*.tr``: block-sparse matmul +
softmax over a block layout): attention that only touches the live
(q-block, kv-block) pairs of a ``SparsityConfig`` layout.

Built on the flash kernel's online-softmax machinery
(``ops/transformer/flash_attention.py``) with one change: the kv grid
dimension walks a *compacted per-row live-block list* instead of all
columns. The lists ride scalar prefetch (``pltpu.PrefetchScalarGridSpec``)
so the k/v BlockSpec index maps can look up the actual kv block index per
grid step — the Pallas/TPU analog of Triton's block-pointer tables, and the
same trick jax's own sparse kernels use. Compute and HBM traffic scale with
``nnz_blocks``, not seq²; rows are padded to the densest row's population
and padded steps are skipped via ``pl.when``.

The backward reuses the flash scheme (dq over the row lists; dk/dv over the
transposed column lists) with lse/delta residuals in the lanes-broadcast
[BN, T, 128] layout.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator import on_tpu

NEG_INF = -1e30


def build_block_tables(layout_h: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Compact a [nq, nk] bool layout into padded live lists.

    Returns (row_idx [nq, Lr], row_cnt [nq], col_idx [nk, Lc], col_cnt [nk]).
    """
    layout_h = np.asarray(layout_h, dtype=bool)
    nq, nk = layout_h.shape

    def compact(mat):
        live = [np.nonzero(mat[r])[0] for r in range(mat.shape[0])]
        width = max(1, max((len(l) for l in live), default=1))
        idx = np.zeros((mat.shape[0], width), dtype=np.int32)
        cnt = np.zeros((mat.shape[0],), dtype=np.int32)
        for r, l in enumerate(live):
            idx[r, : len(l)] = l
            cnt[r] = len(l)
        return idx, cnt

    row_idx, row_cnt = compact(layout_h)
    col_idx, col_cnt = compact(layout_h.T)
    return row_idx, row_cnt, col_idx, col_cnt


def _pair_mask(s, q_blk_i, k_blk_i, blk, causal):
    rows = q_blk_i * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = k_blk_i * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if causal:
        return jnp.where(rows >= cols, s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(row_idx, row_cnt, q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *, scale, blk, width, causal):
    qi = pl.program_id(1)
    li = pl.program_id(2)

    @pl.when(li == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(li < row_cnt[qi])
    def _compute():
        ki = row_idx[qi, li]
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = _pair_mask(s * scale, qi, ki, blk, causal)
        m_prev = m_s[:, :1]
        l_prev = l_s[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        # NEG_INF is finite, so exp(s - m_new) would be 1 (not 0) on rows
        # whose every listed block is causally dead; zero them explicitly so
        # fully-masked rows finish with l=0 → o=0, lse=NEG_INF.
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot(p, v, preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(li == width - 1)
    def _finish():
        l = l_s[:, :1]
        safe_l = jnp.where(l == 0, 1.0, l)
        o_ref[0] = (acc_s[...] / safe_l).astype(o_ref.dtype)
        # fully-masked rows (no live blocks / all-dead causal rows): lse=-inf
        lse = jnp.where(l == 0, NEG_INF, m_s[:, :1] + jnp.log(safe_l))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape).astype(lse_ref.dtype)


def _sparse_fwd(q, k, v, row_idx, row_cnt, scale, blk, causal, interpret):
    BN, T, D = q.shape
    nq, width = row_idx.shape
    kernel = functools.partial(_fwd_kernel, scale=scale, blk=blk, width=width, causal=causal)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(BN, nq, width),
        in_specs=[
            pl.BlockSpec((1, blk, D), lambda b, qi, li, ri, rc: (b, qi, 0)),
            pl.BlockSpec((1, blk, D), lambda b, qi, li, ri, rc: (b, ri[qi, li], 0)),
            pl.BlockSpec((1, blk, D), lambda b, qi, li, ri, rc: (b, ri[qi, li], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk, D), lambda b, qi, li, ri, rc: (b, qi, 0)),
            pl.BlockSpec((1, blk, 128), lambda b, qi, li, ri, rc: (b, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, 128), jnp.float32),
            pltpu.VMEM((blk, 128), jnp.float32),
            pltpu.VMEM((blk, D), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((BN, T, D), q.dtype),
            jax.ShapeDtypeStruct((BN, T, 128), jnp.float32),
        ],
        interpret=interpret,
        **params,
    )(row_idx, row_cnt, q, k, v)
    return o, lse[:, :, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dq_kernel(row_idx, row_cnt, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_s, *, scale, blk, width, causal):
    qi = pl.program_id(1)
    li = pl.program_id(2)

    @pl.when(li == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    @pl.when(li < row_cnt[qi])
    def _compute():
        ki = row_idx[qi, li]
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = _pair_mask(s * scale, qi, ki, blk, causal)
        # masked entries have s=NEG_INF (finite): exp(s - lse) is 1, not 0,
        # when lse is also NEG_INF (fully-masked row) — zero them explicitly
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_s[...] += jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(li == width - 1)
    def _finish():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)


def _dkv_kernel(col_idx, col_cnt, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s, *, scale, blk, width, causal):
    ki = pl.program_id(1)
    li = pl.program_id(2)

    @pl.when(li == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    @pl.when(li < col_cnt[ki])
    def _compute():
        qi = col_idx[ki, li]
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        s = _pair_mask(s * scale, qi, ki, blk, causal)
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - lse), 0.0)
        dv_s[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_s[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(li == width - 1)
    def _finish():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _sparse_bwd(res, g, scale, blk, causal, interpret):
    q, k, v, o, lse, row_idx, row_cnt, col_idx, col_cnt = res
    BN, T, D = q.shape
    nq, width_r = row_idx.shape
    nk, width_c = col_idx.shape
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse[:, :, None], (BN, T, 128))
    delta_b = jnp.broadcast_to(delta[:, :, None], (BN, T, 128))
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, blk=blk, width=width_r, causal=causal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BN, nq, width_r),
            in_specs=[
                pl.BlockSpec((1, blk, D), lambda b, qi, li, ri, rc: (b, qi, 0)),
                pl.BlockSpec((1, blk, D), lambda b, qi, li, ri, rc: (b, ri[qi, li], 0)),
                pl.BlockSpec((1, blk, D), lambda b, qi, li, ri, rc: (b, ri[qi, li], 0)),
                pl.BlockSpec((1, blk, D), lambda b, qi, li, ri, rc: (b, qi, 0)),
                pl.BlockSpec((1, blk, 128), lambda b, qi, li, ri, rc: (b, qi, 0)),
                pl.BlockSpec((1, blk, 128), lambda b, qi, li, ri, rc: (b, qi, 0)),
            ],
            out_specs=pl.BlockSpec((1, blk, D), lambda b, qi, li, ri, rc: (b, qi, 0)),
            scratch_shapes=[pltpu.VMEM((blk, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BN, T, D), q.dtype),
        interpret=interpret,
        **params,
    )(row_idx, row_cnt, q, k, v, do, lse_b, delta_b)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, blk=blk, width=width_c, causal=causal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BN, nk, width_c),
            in_specs=[
                pl.BlockSpec((1, blk, D), lambda b, ki, li, ci, cc: (b, ci[ki, li], 0)),
                pl.BlockSpec((1, blk, D), lambda b, ki, li, ci, cc: (b, ki, 0)),
                pl.BlockSpec((1, blk, D), lambda b, ki, li, ci, cc: (b, ki, 0)),
                pl.BlockSpec((1, blk, D), lambda b, ki, li, ci, cc: (b, ci[ki, li], 0)),
                pl.BlockSpec((1, blk, 128), lambda b, ki, li, ci, cc: (b, ci[ki, li], 0)),
                pl.BlockSpec((1, blk, 128), lambda b, ki, li, ci, cc: (b, ci[ki, li], 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, blk, D), lambda b, ki, li, ci, cc: (b, ki, 0)),
                pl.BlockSpec((1, blk, D), lambda b, ki, li, ci, cc: (b, ki, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((blk, D), jnp.float32),
                pltpu.VMEM((blk, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BN, T, D), k.dtype),
            jax.ShapeDtypeStruct((BN, T, D), v.dtype),
        ],
        interpret=interpret,
        **params,
    )(col_idx, col_cnt, q, k, v, do, lse_b, delta_b)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _sparse_core(q, k, v, row_idx, row_cnt, col_idx, col_cnt, scale, blk, causal, interpret):
    o, _ = _sparse_fwd(q, k, v, row_idx, row_cnt, scale, blk, causal, interpret)
    return o


def _sparse_core_fwd(q, k, v, row_idx, row_cnt, col_idx, col_cnt, scale, blk, causal, interpret):
    o, lse = _sparse_fwd(q, k, v, row_idx, row_cnt, scale, blk, causal, interpret)
    return o, (q, k, v, o, lse, row_idx, row_cnt, col_idx, col_cnt)


def _sparse_core_bwd(scale, blk, causal, interpret, res, g):
    dq, dk, dv = _sparse_bwd(res, g, scale, blk, causal, interpret)
    return dq, dk, dv, None, None, None, None


_sparse_core.defvjp(_sparse_core_fwd, _sparse_core_bwd)


def pallas_block_sparse_attention(
    q: jnp.ndarray,  # [B, NH, T, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    layout: np.ndarray,  # [NH or 1, T/block, T/block] bool
    block: int,
    causal: bool = False,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused block-sparse attention over the layout's live blocks.

    Requirements: T divisible by ``block``; ``block`` a multiple of 8 (TPU
    sublanes). A shared layout (leading dim 1) folds heads into the batch;
    per-head layouts run one kernel per head (different live lists).
    """
    B, NH, T, D = q.shape
    if T % block:
        raise ValueError(f"seq len {T} not divisible by block {block}")
    if block % 8:
        raise ValueError(f"block {block} must be a multiple of 8 (TPU sublanes)")
    scale_f = float(scale) if scale is not None else 1.0 / float(np.sqrt(D))
    if interpret is None:
        interpret = not on_tpu()
    layout = np.asarray(layout, dtype=bool)

    def run(qbn, kbn, vbn, layout_h):
        row_idx, row_cnt, col_idx, col_cnt = build_block_tables(layout_h)
        return _sparse_core(
            qbn, kbn, vbn,
            jnp.asarray(row_idx), jnp.asarray(row_cnt),
            jnp.asarray(col_idx), jnp.asarray(col_cnt),
            scale_f, block, causal, interpret,
        )

    if layout.shape[0] == 1:
        fold = lambda x: x.reshape(B * NH, T, D)
        o = run(fold(q), fold(k), fold(v), layout[0])
        return o.reshape(B, NH, T, D)
    outs = [
        run(q[:, h], k[:, h], v[:, h], layout[h]) for h in range(NH)
    ]
    return jnp.stack(outs, axis=1)
