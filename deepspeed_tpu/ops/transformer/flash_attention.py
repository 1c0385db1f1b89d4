"""Pallas flash attention (TPU).

TPU-native replacement for the reference's fused attention kernels
(``csrc/transformer/softmax_kernels.cu`` + strided-batch-gemm training path
and ``csrc/transformer/inference/csrc/softmax.cu`` softmax_context): one
fused kernel that never materializes the [T, T] score matrix in HBM.

Layout: q/k/v as [BN, T, D] (batch*heads flattened into the leading dim).
Online-softmax forward; the log-sum-exp is saved as a residual and the
backward pass recomputes probabilities blockwise (standard FlashAttention-2
scheme: one kernel for dq accumulating over kv blocks, one for dk/dv
accumulating over q blocks).

**What one grid step holds** follows from ``T``, ``D``, the dtype and a VMEM
budget (``_plan``): where a head's whole sequence fits, a step holds several
heads' q, k, v whole and the body walks heads, the blocks of the *outer* side
(q for the forward and dq, kv for dkv) and, for each, the blocks of the
*walked* side it needs: the causal bound of the outer block, so nothing above
the diagonal costs a grid step or a fetch, and k/v come into VMEM once a
head. The walk has three forms, one body:

* *unrolled*, where a head has ``_UNROLL_PAIRS`` pairs of blocks at most (both
  training cells: T 1,024 is 2 x 2 blocks of 512): the loops over outer and
  walked blocks are ``fori_loop(unroll=True)``, traced once and unrolled by
  the lowering, which then knows every index and keeps of each block only the
  branch it takes (skipped, masked, unmasked). A head is one straight line of
  three blocks' products and softmax, which the compiler schedules across.
  This is where the time went: a block's products depend on each other
  through the softmax, a rolled loop drains the MXU between them, and the
  same blocks take 1.7 times as long rolled (``PERF.md`` section 6, PRs 32-33);
* *rolled*, for a longer head: two ``fori_loop``s an outer block, over the
  walked blocks below the diagonal and then over those it crosses, their trip
  counts traced scalars. Lowering and compiling grow with the number of
  blocks unrolled (64 pairs at T 4,096: 20 s of compiling), so this form is
  what keeps set-up flat;
* *streaming*, for a sequence too long to hold: the grid's second dimension
  takes one outer block a step, its third takes the walked side in chunks of
  as many blocks as fit, walked rolled, and the running state crosses those
  steps in VMEM scratch (chunks wholly above the diagonal are not fetched:
  the index map stays on the nearest chunk the block needs).

**A block** costs the two (forward), three (dq) or four (dkv) matrix products
and little else: ``scale`` is folded into the outer side's operand once an
outer block (q for the forward and dq, k for dkv; the gradient is scaled once
at the end), the causal mask is applied only on blocks the diagonal crosses,
and the running max, sum and accumulator are loop values. At D = 64 a product
fills half the MXU's depth (``q k^T``) or width (``p v``), and unrolled the
kernels run within a tenth of that bound. Operands stay in their own dtype
(bf16 in training: the MXU multiplies bf16 at full rate and accumulates
float32 through ``preferred_element_type``; a float32 cast would force
1/8-rate passes, measured 20 against 197 TFLOP/s on v5e); softmax statistics,
``p`` and ``ds`` are float32 until they are cast to the operand dtype for
their product.

**Row statistics** (the forward's log-sum-exp, the backward's ``delta``) are
``f32[BN, 1, T]``: lane-dense, 4 bytes a row, written once by the forward and
read as they are by both backward kernels (they were ``[BN, T, 128]`` with
128 equal lanes, broadcast again by XLA before the backward: 0.4 GB a layer
call at GPT-2 125M for 0.4 MB of information). The forward and dq hold a
block's statistics as a column (one a score row) and turn it to and from the
stored row with one small transpose an outer block; dkv computes its blocks
transposed (``k q^T``), so a ``[1, blk_q]`` statistic broadcasts along
sublanes as stored and ``p^T do`` and ``ds^T q`` need no transposed operand.

The three ``pallas_call``s are named (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``): the name is the custom call's instruction name in the
compiled HLO and a component of its op name stack, which is how a profiler
trace finds each kernel (``benchmark/op_scopes.py``); the benchmark's other
readers tell them by operand and result counts (3 -> (o, f32), 6 -> 1,
6 -> 2), which therefore stay as they are. ``tools/flash_kernel_bench.py``
times the three alone on the chip; it chose the constants below.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator import on_tpu

NEG_INF = -1e30
_LANES = 128
# the longest block a side takes, whatever the caller allows (256 x 256 is 8%
# faster unrolled at T 1,024 and twice the lowering; 256 x 512 and 512 x 256 are slower)
_BLOCK_Q = 512
_BLOCK_K = 512
_MAX_HEADS = 8  # heads a grid step holds at most (1 to 8 time the same)
# a head's walk is unrolled where it has this many pairs of blocks at most: T 2,048 at blocks of 512
_UNROLL_PAIRS = 16
# bytes a step's operand blocks (double-buffered by the pipeline) and scratch
# may take of VMEM; the compiler's limit leaves room for a block's
# intermediates beside them
_VMEM_BUDGET = 24 << 20
_VMEM_INTERMEDIATES = 24 << 20

_NT = (((1,), (1,)), ((), ()))  # a @ b^T


class _Plan(NamedTuple):
    """What a grid step holds: ``heads`` heads, ``outer`` rows of the outer
    side and ``walked`` rows of the walked side; ``streams`` where the walked
    side takes more than one grid step."""

    heads: int
    outer: int
    walked: int
    streams: bool


class _How(NamedTuple):
    """What a call fixes before it is traced (the custom VJP's one static argument)."""

    scale: float
    causal: bool
    blk_q: int
    blk_k: int
    interpret: bool
    vmem_budget: int
    unroll_pairs: int


def _largest_divisor(n: int, limit: int) -> int:
    """The largest divisor of ``n`` that is at most ``limit``."""
    return next(d for d in range(min(n, limit), 0, -1) if n % d == 0)


def _plan(BN, T, D, itemsize, blk_outer, blk_walked, tensors_outer, tensors_walked, vmem_budget) -> _Plan:
    """A head's whole sequence a step where ``vmem_budget`` holds it (then as
    many heads as fit), else one outer block and the largest chunk of walked
    blocks that fits."""
    row = -(-D // _LANES) * _LANES * itemsize  # a row of a [T, D] block in VMEM: whole lanes
    stats = 2 * 8 * 4  # two [1, T] float32 statistics at most, a sublane tile high

    def head_bytes(outer, walked):
        state = 0 if walked == T else outer * (2 * _LANES + _LANES) * 4  # m, l, accumulators
        return 2 * (tensors_outer * outer + tensors_walked * walked) * row + 2 * stats * max(outer, walked) + state

    outer, walked = T, T
    if head_bytes(T, T) > vmem_budget:
        outer = blk_outer
        fits = [c for c in range(blk_walked, T + 1, blk_walked) if T % c == 0 and head_bytes(outer, c) <= vmem_budget]
        walked = max(fits, default=blk_walked)
    heads = _largest_divisor(BN, min(_MAX_HEADS, max(1, vmem_budget // head_bytes(outer, walked))))
    return _Plan(heads, outer, walked, walked != T)


def _span(i, blk):
    """Rows ``i * blk .. (i + 1) * blk`` of a ref, ``i`` a traced scalar."""
    return pl.ds(pl.multiple_of(lax.mul(i, blk), blk), blk)


def _scaled(x, scale):
    """``x * scale`` in ``x``'s dtype, once an outer block."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _as_row(col):
    """``[n, 1]`` -> ``[1, n]``: a statistic on its way to the lane-dense store."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1, :]


def _as_col(row):
    """``[1, n]`` -> ``[n, 1]``: a stored statistic beside a block's score rows."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T[:, :1]


def _below_diagonal(row0, col0, shape, rows_dim):
    """Where a score block whose first query is ``row0`` and first key ``col0``
    is visible: query position >= key position. ``rows_dim`` is the block's
    query dimension (0, or 1 for a transposed block)."""
    rows = lax.broadcasted_iota(jnp.int32, shape, rows_dim)
    cols = lax.broadcasted_iota(jnp.int32, shape, 1 - rows_dim)
    return lax.ge(lax.add(rows, lax.sub(row0, col0)), cols)


def _walk(n, lo, mid, hi, masked_first, step, carry, unrolled):
    """``step(masked)(i, carry)`` over blocks ``lo .. mid`` and ``mid .. hi``
    of the ``n`` walked, the masked part first or last. Rolled: two loops
    whose trip counts are the causal bounds of one outer block. Unrolled (the
    walk and the loop around it: every index is a constant when the kernel is
    lowered, so each block's branch is chosen then): one straight line of the
    blocks the outer block needs, which the compiler schedules across."""
    if not unrolled:
        carry = lax.fori_loop(lo, mid, step(masked_first), carry)
        return lax.fori_loop(mid, hi, step(not masked_first), carry)

    def block(i, carry):
        outside = lax.bitwise_or(lax.lt(i, lo), lax.ge(i, hi))
        which = lax.select(outside, 0, lax.select(lax.lt(i, mid), 1, 2))
        return lax.switch(which, [lambda i, carry: carry, step(masked_first), step(not masked_first)], i, carry)

    return lax.fori_loop(0, n, block, carry, unroll=True)


def _diagonal_blocks(ahead, blk_outer, blk_walked, n):
    """Of the ``n`` walked blocks, how many end at or before position
    ``ahead`` (counted from the first walked position) and how many start
    before ``ahead + blk_outer - 1``: the walked blocks wholly on one side of
    an outer block's stretch of the diagonal, and those that reach it."""
    before = lax.clamp(0, lax.div(ahead, blk_walked), n)
    reach = lax.clamp(0, lax.div(lax.add(ahead, blk_outer + blk_walked - 2), blk_walked), n)
    return before, reach


def _resume(carry, state, g, first):
    """A streaming walk's running values: fresh at the walked side's first
    chunk, else what the step before left in ``state`` for head ``g``."""
    return tuple(jnp.where(first, init, s[g]) for init, s in zip(carry, state)) if state else carry


def _leave(carry, state, g, last, finish):
    """``finish`` now, or (streaming) leave ``carry`` in ``state`` and finish
    at the walked side's last chunk."""
    if not state:
        return finish()
    for s, x in zip(state, carry):
        s[g] = x
    pl.when(last)(finish)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *state, scale, causal, blk_q, blk_k, unrolled):
    G, Lq, D = q_ref.shape
    nkb = k_ref.shape[1] // blk_k
    row_base = lax.mul(pl.program_id(1), Lq)  # this step's first query
    col_base = lax.mul(pl.program_id(2), k_ref.shape[1])  # and first key
    first, last = pl.program_id(2) == 0, pl.program_id(2) == pl.num_programs(2) - 1

    def head(g, _):
        def q_block(qb, _):
            rows = _span(qb, blk_q)
            row0 = lax.add(row_base, lax.mul(qb, blk_q))
            q = _scaled(q_ref[g, rows, :], scale)
            carry = (
                jnp.full((blk_q, 1), NEG_INF, jnp.float32),
                jnp.zeros((blk_q, 1), jnp.float32),
                jnp.zeros((blk_q, D), jnp.float32),
            )
            carry = _resume(carry, state, g, first)

            def step(masked):
                def kv_block(kb, carry):
                    m, l, acc = carry
                    cols = _span(kb, blk_k)
                    v = v_ref[g, cols, :]
                    s = lax.dot_general(q, k_ref[g, cols, :], _NT, preferred_element_type=jnp.float32)
                    if masked:
                        col0 = lax.add(col_base, lax.mul(kb, blk_k))
                        s = jnp.where(_below_diagonal(row0, col0, s.shape, 0), s, NEG_INF)
                    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                    corr = jnp.exp(m - m_new)
                    p = jnp.exp(s - m_new)
                    l = corr * l + jnp.sum(p, axis=1, keepdims=True)
                    acc = acc * corr + lax.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
                    return m_new, l, acc

                return kv_block

            # key blocks wholly at or below the block's first query, then those the diagonal crosses
            n_full, n_any = _diagonal_blocks(lax.sub(lax.add(row0, 1), col_base), blk_q, blk_k, nkb) if causal else (nkb, nkb)
            m, l, acc = _walk(nkb, 0, n_full, n_any, False, step, carry, unrolled)

            def finish():
                safe_l = jnp.where(l == 0, 1.0, l)
                o_ref[g, rows, :] = (acc / safe_l).astype(o_ref.dtype)
                lse_ref[g, :, rows] = _as_row(m + jnp.log(safe_l))

            _leave((m, l, acc), state, g, last, finish)
            return _

        return lax.fori_loop(0, Lq // blk_q, q_block, _, unroll=unrolled)

    lax.fori_loop(0, G, head, None)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *state, scale, causal, blk_q, blk_k, unrolled):
    G, Lq, D = q_ref.shape
    nkb = k_ref.shape[1] // blk_k
    row_base = lax.mul(pl.program_id(1), Lq)
    col_base = lax.mul(pl.program_id(2), k_ref.shape[1])
    first, last = pl.program_id(2) == 0, pl.program_id(2) == pl.num_programs(2) - 1

    def head(g, _):
        def q_block(qb, _):
            rows = _span(qb, blk_q)
            row0 = lax.add(row_base, lax.mul(qb, blk_q))
            q = _scaled(q_ref[g, rows, :], scale)
            do = do_ref[g, rows, :]
            lse = _as_col(lse_ref[g, :, rows])
            delta = _as_col(delta_ref[g, :, rows])
            carry = _resume((jnp.zeros((blk_q, D), jnp.float32),), state, g, first)

            def step(masked):
                def kv_block(kb, carry):
                    (dq,) = carry
                    cols = _span(kb, blk_k)
                    k = k_ref[g, cols, :]
                    s = lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
                    if masked:
                        col0 = lax.add(col_base, lax.mul(kb, blk_k))
                        s = jnp.where(_below_diagonal(row0, col0, s.shape, 0), s, NEG_INF)
                    p = jnp.exp(s - lse)
                    dp = lax.dot_general(do, v_ref[g, cols, :], _NT, preferred_element_type=jnp.float32)
                    ds = (p * (dp - delta)).astype(k.dtype)
                    return (dq + lax.dot(ds, k, preferred_element_type=jnp.float32),)

                return kv_block

            n_full, n_any = _diagonal_blocks(lax.sub(lax.add(row0, 1), col_base), blk_q, blk_k, nkb) if causal else (nkb, nkb)
            (dq,) = _walk(nkb, 0, n_full, n_any, False, step, carry, unrolled)

            def finish():
                dq_ref[g, rows, :] = (dq * scale).astype(dq_ref.dtype)

            _leave((dq,), state, g, last, finish)
            return _

        return lax.fori_loop(0, Lq // blk_q, q_block, _, unroll=unrolled)

    lax.fori_loop(0, G, head, None)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *state, scale, causal, blk_q, blk_k, unrolled):
    G, Lk, D = k_ref.shape
    nqb = q_ref.shape[1] // blk_q
    col_base = lax.mul(pl.program_id(1), Lk)
    row_base = lax.mul(pl.program_id(2), q_ref.shape[1])
    first, last = pl.program_id(2) == 0, pl.program_id(2) == pl.num_programs(2) - 1

    def head(g, _):
        def kv_block(kb, _):
            cols = _span(kb, blk_k)
            col0 = lax.add(col_base, lax.mul(kb, blk_k))
            k = _scaled(k_ref[g, cols, :], scale)
            v = v_ref[g, cols, :]
            carry = _resume((jnp.zeros((blk_k, D), jnp.float32), jnp.zeros((blk_k, D), jnp.float32)), state, g, first)

            def step(masked):
                def q_block(qb, carry):
                    dk, dv = carry
                    rows = _span(qb, blk_q)
                    q = q_ref[g, rows, :]
                    do = do_ref[g, rows, :]
                    # the block transposed, [blk_k, blk_q]: a query's statistic is a lane's
                    s = lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
                    if masked:
                        row0 = lax.add(row_base, lax.mul(qb, blk_q))
                        s = jnp.where(_below_diagonal(row0, col0, s.shape, 1), s, NEG_INF)
                    p = jnp.exp(s - lse_ref[g, :, rows])
                    dv = dv + lax.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
                    dp = lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
                    ds = (p * (dp - delta_ref[g, :, rows])).astype(q.dtype)
                    return dk + lax.dot(ds, q, preferred_element_type=jnp.float32), dv

                return q_block

            # query blocks wholly before the block's first key see none of it; then those the diagonal crosses, then the rest
            n_none, n_masked = _diagonal_blocks(lax.sub(col0, row_base), blk_k, blk_q, nqb) if causal else (0, 0)
            dk, dv = _walk(nqb, n_none, n_masked, nqb, True, step, carry, unrolled)

            def finish():
                dk_ref[g, cols, :] = (dk * scale).astype(dk_ref.dtype)
                dv_ref[g, cols, :] = dv.astype(dv_ref.dtype)

            _leave((dk, dv), state, g, last, finish)
            return _

        return lax.fori_loop(0, Lk // blk_k, kv_block, _, unroll=unrolled)

    lax.fori_loop(0, G, head, None)


def _call(kernel, name, operands, stats, out_dtypes, q_outer, state_cols, how: _How):
    """One of the three kernels over ``[BN // heads, outer steps, walked
    steps]``. ``operands`` are ``("q" | "k", [BN, T, D] array)``: the side whose
    rows the operand follows; ``stats`` are the ``[BN, 1, T]`` row statistics
    it reads (it writes one where it reads none: the forward); the results
    follow the outer side, the queries' where ``q_outer``; ``state_cols``
    are the widths of what a streaming walk carries from step to step."""
    scale, causal, blk_q, blk_k, interpret, vmem_budget, unroll_pairs = how
    BN, T, D = operands[0][1].shape
    outer_side = "q" if q_outer else "k"
    blk_outer, blk_walked = (blk_q, blk_k) if q_outer else (blk_k, blk_q)
    held_outer = sum(side == outer_side for side, _ in operands) + len(out_dtypes)  # tensors whose outer rows a step holds
    G, Lo, Lw, streams = _plan(
        BN, T, D, operands[0][1].dtype.itemsize, blk_outer, blk_walked, held_outer, len(operands) + len(out_dtypes) - held_outer, vmem_budget
    )

    def outer_map(b, i, j):
        return (b, i, 0)

    def walked_map(b, i, j):
        if causal and streams:
            # a chunk the outer block sees nothing of is not fetched: the map stays on the nearest it needs
            if q_outer:
                j = lax.min(j, lax.div(lax.add(lax.mul(i, Lo), Lo - 1), Lw))
            else:
                j = lax.max(j, lax.div(lax.mul(i, Lo), Lw))
        return (b, j, 0)

    def stat_map(b, i, j):
        return (b, 0, (outer_map if q_outer else walked_map)(b, i, j)[1])

    stat_spec = pl.BlockSpec((G, 1, Lo if q_outer else Lw), stat_map)
    in_specs = [
        pl.BlockSpec((G, Lo, D), outer_map) if side == outer_side else pl.BlockSpec((G, Lw, D), walked_map)
        for side, _ in operands
    ] + [stat_spec] * len(stats)
    out_specs = [pl.BlockSpec((G, Lo, D), outer_map) for _ in out_dtypes]
    out_shape = [jax.ShapeDtypeStruct((BN, T, D), dtype) for dtype in out_dtypes]
    if not stats:
        out_specs.append(stat_spec)
        out_shape.append(jax.ShapeDtypeStruct((BN, 1, T), jnp.float32))
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_budget + _VMEM_INTERMEDIATES,
        )
    return pl.pallas_call(
        functools.partial(
            kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
            # only a whole head a step has constant block indices to unroll over
            unrolled=Lo == Lw == T and (T // blk_outer) * (T // blk_walked) <= unroll_pairs,
        ),
        grid=(BN // G, T // Lo, T // Lw),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((G, Lo, cols), jnp.float32) for cols in state_cols] if streams else [],
        interpret=interpret,
        name=name,
        **params,
    )(*(x for _, x in operands), *stats)


def _flash_fwd(q, k, v, how):
    D = q.shape[-1]
    return _call(_fwd_kernel, "flash_fwd", [("q", q), ("k", k), ("k", v)], [], [q.dtype], True, (1, 1, D), how)


def _flash_bwd(how, res, do):
    q, k, v, o, lse = res
    D = q.shape[-1]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)[:, None, :]  # [BN, 1, T], as lse is
    operands = [("q", q), ("k", k), ("k", v), ("q", do)]
    (dq,) = _call(_dq_kernel, "flash_bwd_dq", operands, [lse, delta], [q.dtype], True, (D,), how)
    dk, dv = _call(_dkv_kernel, "flash_bwd_dkv", operands, [lse, delta], [k.dtype, v.dtype], False, (D, D), how)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_core(q, k, v, how):
    o, _ = _flash_fwd(q, k, v, how)
    return o


def _flash_core_fwd(q, k, v, how):
    o, lse = _flash_fwd(q, k, v, how)
    return o, (q, k, v, o, lse)


_flash_core.defvjp(_flash_core_fwd, _flash_bwd)


def _block(T: int, limit: int, causal: bool) -> int:
    """The block a side of ``T`` positions takes, ``limit`` at most: whole
    lanes of row statistics where ``T`` has them. A causal sequence is padded
    up to its blocks; any other must be tiled by them."""
    if causal:
        return min(limit, -(-T // _LANES) * _LANES)
    tiles = [b for b in range(_LANES, min(limit, T) + 1, _LANES) if T % b == 0]
    return tiles[-1] if tiles else min(limit, T)


def flash_attention(
    q,
    k,
    v,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
):
    """Fused attention over [B, T, N, D] (heads-last layout like the model).

    GQA inputs (fewer kv heads) must be pre-expanded by the caller. The
    sequence is padded up to the block size; padded kv columns sit above the
    causal diagonal of every real row, and padded q rows are sliced off on
    return. ``block_q`` / ``block_k`` are upper limits: the kernels take the
    blocks the kernel bench chose, where those are shorter.
    """
    return _flash_attention(q, k, v, causal, scale, block_q, block_k, interpret, _VMEM_BUDGET, _UNROLL_PAIRS)


def _flash_attention(q, k, v, causal, scale, block_q, block_k, interpret, vmem_budget, unroll_pairs):
    """``flash_attention`` with the VMEM a grid step may hold and the pairs of
    blocks a walk unrolls at most as arguments (the tests' way to the
    streaming grid and the rolled walk at a short ``T``)."""
    B, T, N, D = q.shape
    assert k.shape == v.shape == (B, T, N, D), "flash_attention requires equal q/kv heads"
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    if interpret is None:
        interpret = not on_tpu()

    blk_q = _block(T, min(block_q, _BLOCK_Q), causal)
    blk_k = _block(T, min(block_k, _BLOCK_K), causal)
    # both block sizes must divide the padded length or grid truncation would
    # silently drop trailing blocks
    pad = (-T) % math.lcm(blk_q, blk_k)
    if pad and not causal:
        raise ValueError("non-causal flash attention requires T divisible by the block sizes")
    padded_T = T + pad
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))) for x in (q, k, v))

    def to_bn(x):
        return x.transpose(0, 2, 1, 3).reshape(B * N, padded_T, D)

    o = _flash_core(to_bn(q), to_bn(k), to_bn(v), _How(float(scale), causal, blk_q, blk_k, interpret, vmem_budget, unroll_pairs))
    o = o.reshape(B, N, padded_T, D).transpose(0, 2, 1, 3)
    if pad:
        o = o[:, :T]
    return o
