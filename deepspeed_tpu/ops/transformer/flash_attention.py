"""Pallas flash attention (TPU).

TPU-native replacement for the reference's fused attention kernels
(``csrc/transformer/softmax_kernels.cu`` + strided-batch-gemm training path
and ``csrc/transformer/inference/csrc/softmax.cu`` softmax_context): one
fused kernel that never materializes the [T, T] score matrix in HBM.

Layout: q, k, v and o as ``[B, T, N * D]``, the layout the model's
projections write and read (``h @ wq`` leaves it, ``attn @ wo`` takes it; the
``[B, T, N, D]`` the entry takes is a free reshape of it), so no copy stands
between a projection and a kernel, forward or backward, and the residuals
are saved as they came (until PR 60 the kernels were entered head-major,
``[B * N, T, D]``: twelve copies a layer around the three calls, 35 ms of
GPT-2 XL's 880 ms step). The unit is a **tile** of a batch row's lanes
(``operand_layout``): a head where a head is whole lane tiles (D 128, 256),
the heads of one lane tile where they divide it (two heads of 64); a grid
step takes up to ``_MAX_HEADS`` heads of them side by side (``_heads_a_step``)
and a head's operands are its tile of the step's blocks, a slice at a whole
number of lane tiles. The products stay whole-tile, with no lane shuffle: a
contraction over the tile's lanes against an operand whose other heads'
lanes are *selected* to zero (``_head_lanes``, on the outer side, once an
outer block) is one head's ``q k^T``, at the cost the 64-lane product has on
a unit 128 deep; a product that contracts over rows (``p v``, ``ds k``,
``p^T do``, ``ds^T q``) gives the tile's lanes whole, of which the head's are
kept when the result is stored (``_store_head``). Static 64-lane slices of
the refs are the other form (Mosaic takes them): at GPT-2 XL's shape the
kernels read 3% slower with them than with the selects, and their body is
traced once a head of the tile (``PERF.md`` section 6, PR 60). Where the
heads do not fill the last step (GPT-2 XL: 25 heads of 64 are 12.5 tiles)
the walk over heads stops at the last real one and the walked side's
operands have the lanes past it selected to zero: what lies there is
unspecified, and both sides of a contraction must be numbers. A head width
that neither divides a lane tile nor is divided by one (80, 96) is
transposed to ``[B * N, T, D]`` at the door and runs the same kernels with
its ``D`` lanes as the tile.

Online-softmax forward; the log-sum-exp is saved as a residual and the
backward pass recomputes probabilities blockwise (standard FlashAttention-2
scheme: one kernel for dq accumulating over kv blocks, one for dk/dv
accumulating over q blocks).

**What one grid step holds** follows from ``T``, the heads' lanes, the dtype
and a VMEM budget (``_heads_a_step``, ``_plan``): where the whole sequence of
several tiles fits, a step holds their q, k, v whole and the body walks the
step's heads (a rolled loop, one body for them all: the lowering does not
grow with them), the blocks of the *outer* side (q for the forward and dq, kv
for dkv) and, for each, the blocks of the *walked* side it needs: the causal
bound of the outer block, so nothing above the diagonal costs a grid step or
a fetch, and k/v come into VMEM once a step. The walk has three forms, one
body:

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
``f32[B, N, 1, T]``: lane-dense, 4 bytes a row, written once by the forward and
read as they are by both backward kernels (they were ``[BN, T, 128]`` with
128 equal lanes, broadcast again by XLA before the backward: 0.4 GB a layer
call at GPT-2 125M for 0.4 MB of information). ``delta`` is made outside the
three kernels, by a small fourth (``flash_bwd_delta``: the matrix unit sums a
head's lanes of ``do * o`` and leaves the sums along lanes); left to XLA the
reduction takes ``do`` and the saved ``o`` with ``T`` minor, and the kernels
get a copy of each. The forward and dq hold a
block's statistics as a column (one a score row) and turn it to and from the
stored row with one small transpose an outer block; dkv computes its blocks
transposed (``k q^T``), so a ``[1, blk_q]`` statistic broadcasts along
sublanes as stored and ``p^T do`` and ``ds^T q`` need no transposed operand.

The three attention ``pallas_call``s are named (``flash_fwd``, ``flash_bwd_dq``,
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
# heads a grid step holds at most, lane tile beside lane tile, and the bytes of operand blocks (double-buffered) such a
# step may take: a grid step costs ~0.3 us whatever it holds, and past ~16 MB the backward's blocks crowd out their own
# intermediates (dkv at 25 MB a step reads 40% slower at D 128 and at D 64 alike; tools/flash_kernel_bench.py, PR 60)
_MAX_HEADS = 8
_STEP_BYTES = 16 << 20
# a head's walk is unrolled where it has this many pairs of blocks at most: T 2,048 at blocks of 512
_UNROLL_PAIRS = 16
# bytes a step's operand blocks (double-buffered by the pipeline) and scratch
# may take of VMEM; the compiler's limit leaves room for a block's
# intermediates beside them
_VMEM_BUDGET = 24 << 20
_VMEM_INTERMEDIATES = 24 << 20

_NT = (((1,), (1,)), ((), ()))  # a @ b^T


class OperandLayout(NamedTuple):
    """How the kernels address q, k, v, o (``operand_layout``)."""

    path: str  # "lanes": [B, T, N * D] as the projections leave it; "head_major": [B * N, T, D], transposed at the door
    heads_per_lane_tile: int  # heads that share a tile of lanes (a grid step holds several tiles)
    edge_tile: bool  # the operand's last tile holds fewer heads than that


def operand_layout(heads: int, head_dim: int) -> OperandLayout:
    """The layout ``flash_attention`` takes for ``heads`` heads (those a chip
    holds) of ``head_dim``: a pure function of the shape, which the entry
    itself calls and the engine records at build time (``flash.operand_layout``).
    A head of whole lane tiles is a tile of its own; heads that divide a
    lane tile share one, as many as fit; any other width (80, 96, ...) keeps
    the head-major entry, whose tile is a head's full ``D``."""
    if head_dim % _LANES == 0:
        return OperandLayout("lanes", 1, False)
    if _LANES % head_dim == 0:
        per_tile = min(_LANES // head_dim, heads)
        return OperandLayout("lanes", per_tile, heads % per_tile != 0)
    return OperandLayout("head_major", 1, False)


class _Plan(NamedTuple):
    """What a grid step holds of its heads: ``outer`` rows of the outer
    side and ``walked`` rows of the walked side; ``streams`` where the walked
    side takes more than one grid step."""

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
    head_dim: int


class _Heads(NamedTuple):
    """The heads a grid step holds: ``tiles`` tiles side by side, each
    ``per_tile`` heads of ``dim`` lanes (a lane tile of two heads of 64, or one
    head of whole lane tiles); ``total`` heads in the operand where its last
    tile holds fewer than ``per_tile`` (lanes that are not there), else None:
    every step is full."""

    per_tile: int
    dim: int
    tiles: int
    total: int | None

    @property
    def lanes(self) -> int:  # of a tile
        return self.per_tile * self.dim

    @property
    def a_step(self) -> int:
        return self.tiles * self.per_tile


def _heads_a_step(lanes: int, head_dim: int, fits) -> tuple[_Heads, int]:
    """The heads a grid step holds of an operand of ``lanes`` lanes
    (``N * D``), and the steps that takes: the most tiles that divide the
    operand's (every step is full: 6 tiles go 3 a step, GPT-2 XL's 13 one a
    step, which its cell's step reads 0.3% faster than four and a short last
    step), hold ``_MAX_HEADS`` heads at most and ``fits(lanes of the step)``."""
    N = lanes // head_dim
    _, per_tile, ragged = operand_layout(N, head_dim)
    tile = per_tile * head_dim
    n_tiles = -(-N // per_tile)
    most = min(max(1, _MAX_HEADS // per_tile), n_tiles) if tile % _LANES == 0 else 1  # else the tile is the operand's full width
    tiles = next(t for t in range(most, 0, -1) if n_tiles % t == 0 and (t == 1 or fits(t * tile)))
    return _Heads(per_tile, head_dim, tiles, N if ragged else None), n_tiles // tiles


def _plan(T, W, heads, itemsize, blk_outer, blk_walked, tensors_outer, tensors_walked, vmem_budget) -> _Plan:
    """The whole sequence of a block of ``W`` lanes (``heads`` heads) a step
    where ``vmem_budget`` holds it, else one outer block and the largest chunk
    of walked blocks that fits."""
    row = -(-W // _LANES) * _LANES * itemsize  # a row of a [T, W] block in VMEM: whole lanes
    stats = heads * 2 * 8 * 4  # two [1, T] float32 statistics a head at most, a sublane tile high

    def step_bytes(outer, walked):
        state = 0 if walked == T else heads * outer * (2 * _LANES + row // itemsize) * 4  # m, l, accumulators
        return 2 * (tensors_outer * outer + tensors_walked * walked) * row + 2 * stats * max(outer, walked) + state

    outer, walked = T, T
    if step_bytes(T, T) > vmem_budget:
        outer = blk_outer
        fits = [c for c in range(blk_walked, T + 1, blk_walked) if T % c == 0 and step_bytes(outer, c) <= vmem_budget]
        walked = max(fits, default=blk_walked)
    return _Plan(outer, walked, walked != T)


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


def _heads_here(heads: _Heads):
    """Heads in this grid step: fewer in the operand's last step where the
    heads do not fill it (GPT-2 XL's 25 heads of 64 are 12.5 lane tiles), so
    the walk over heads never enters a head that is not there."""
    if heads.total is None:
        return heads.a_step
    return lax.min(heads.a_step, lax.sub(heads.total, lax.mul(pl.program_id(1), heads.a_step)))


def _head_in_step(g, n_here, heads: _Heads):
    """Of head ``g`` of a grid step: its tile's lanes in the step's blocks (a
    slice at a whole number of tiles), its place ``h`` in the tile, and the
    lanes of its tile that hold heads (None where every tile is full)."""
    if heads.tiles == 1:
        lanes, h = slice(None), g
    else:
        tile = lax.div(g, heads.per_tile)
        lanes, h = pl.ds(pl.multiple_of(lax.mul(tile, heads.lanes), heads.lanes), heads.lanes), lax.rem(g, heads.per_tile)
    return lanes, h, lax.mul(lax.sub(n_here, lax.sub(g, h)), heads.dim) if heads.total is not None else None


def _lanes_of(x, lo, hi, other=None):
    """``x`` on lanes ``lo .. hi``, ``other`` (0) on the rest: a select, never
    a product by a 0/1 mask (what lies beyond an operand's last lane is
    unspecified, and 0 x NaN is NaN)."""
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lax.bitwise_and(lax.ge(lane, lo), lax.lt(lane, hi)), x, jnp.zeros_like(x) if other is None else other)


def _head_lanes(x, h, heads: _Heads):
    """Head ``h``'s lanes of a tile, the other heads' zeroed: a contraction
    over all the tile's lanes against it is head ``h``'s alone, at the cost
    the contraction over ``dim`` lanes has on a unit 128 deep. It is taken on
    the outer side's operands, once an outer block."""
    if heads.per_tile == 1:
        return x
    return _lanes_of(x, lax.mul(h, heads.dim), lax.mul(lax.add(h, 1), heads.dim))


def _real_lanes(x, real):
    """A walked block with the lanes beyond the operand's last head zeroed
    (they meet the outer side's zeros in a contraction, and must be numbers);
    the block as it is where every tile is full (``real`` None)."""
    return x if real is None else _lanes_of(x, 0, real)


def _store_head(ref, rows, lanes, x, h, heads: _Heads):
    """Head ``h``'s lanes of ``x`` into its tile of ``ref[0, rows]``, the
    tile's other heads left as they are."""
    if heads.per_tile > 1:
        x = _lanes_of(x, lax.mul(h, heads.dim), lax.mul(lax.add(h, 1), heads.dim), other=ref[0, rows, lanes])
    ref[0, rows, lanes] = x


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *state, scale, causal, blk_q, blk_k, unrolled, heads):
    Lq, W = q_ref.shape[1], heads.lanes
    nkb = k_ref.shape[1] // blk_k
    row_base = lax.mul(pl.program_id(2), Lq)  # this step's first query
    col_base = lax.mul(pl.program_id(3), k_ref.shape[1])  # and first key
    first, last = pl.program_id(3) == 0, pl.program_id(3) == pl.num_programs(3) - 1
    n_here = _heads_here(heads)

    def head(g, _):
        lanes, h, real = _head_in_step(g, n_here, heads)

        def q_block(qb, _):
            rows = _span(qb, blk_q)
            row0 = lax.add(row_base, lax.mul(qb, blk_q))
            q = _head_lanes(_scaled(q_ref[0, rows, lanes], scale), h, heads)
            carry = (
                jnp.full((blk_q, 1), NEG_INF, jnp.float32),
                jnp.zeros((blk_q, 1), jnp.float32),
                jnp.zeros((blk_q, W), jnp.float32),  # head h's lanes are its output; the others are not read
            )
            carry = _resume(carry, state, g, first)

            def step(masked):
                def kv_block(kb, carry):
                    m, l, acc = carry
                    cols = _span(kb, blk_k)
                    v = v_ref[0, cols, lanes]
                    s = lax.dot_general(q, _real_lanes(k_ref[0, cols, lanes], real), _NT, preferred_element_type=jnp.float32)
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
                _store_head(o_ref, rows, lanes, (acc / safe_l).astype(o_ref.dtype), h, heads)
                lse_ref[0, g, :, rows] = _as_row(m + jnp.log(safe_l))

            _leave((m, l, acc), state, g, last, finish)
            return _

        return lax.fori_loop(0, Lq // blk_q, q_block, _, unroll=unrolled)

    lax.fori_loop(0, n_here, head, None)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *state, scale, causal, blk_q, blk_k, unrolled, heads):
    Lq, W = q_ref.shape[1], heads.lanes
    nkb = k_ref.shape[1] // blk_k
    row_base = lax.mul(pl.program_id(2), Lq)
    col_base = lax.mul(pl.program_id(3), k_ref.shape[1])
    first, last = pl.program_id(3) == 0, pl.program_id(3) == pl.num_programs(3) - 1
    n_here = _heads_here(heads)

    def head(g, _):
        lanes, h, real = _head_in_step(g, n_here, heads)

        def q_block(qb, _):
            rows = _span(qb, blk_q)
            row0 = lax.add(row_base, lax.mul(qb, blk_q))
            q = _head_lanes(_scaled(q_ref[0, rows, lanes], scale), h, heads)
            do = _head_lanes(do_ref[0, rows, lanes], h, heads)
            lse = _as_col(lse_ref[0, g, :, rows])
            delta = _as_col(delta_ref[0, g, :, rows])
            carry = _resume((jnp.zeros((blk_q, W), jnp.float32),), state, g, first)

            def step(masked):
                def kv_block(kb, carry):
                    (dq,) = carry
                    cols = _span(kb, blk_k)
                    k = _real_lanes(k_ref[0, cols, lanes], real)
                    s = lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
                    if masked:
                        col0 = lax.add(col_base, lax.mul(kb, blk_k))
                        s = jnp.where(_below_diagonal(row0, col0, s.shape, 0), s, NEG_INF)
                    p = jnp.exp(s - lse)
                    dp = lax.dot_general(do, _real_lanes(v_ref[0, cols, lanes], real), _NT, preferred_element_type=jnp.float32)
                    ds = (p * (dp - delta)).astype(k.dtype)
                    return (dq + lax.dot(ds, k, preferred_element_type=jnp.float32),)

                return kv_block

            n_full, n_any = _diagonal_blocks(lax.sub(lax.add(row0, 1), col_base), blk_q, blk_k, nkb) if causal else (nkb, nkb)
            (dq,) = _walk(nkb, 0, n_full, n_any, False, step, carry, unrolled)

            def finish():
                _store_head(dq_ref, rows, lanes, (dq * scale).astype(dq_ref.dtype), h, heads)

            _leave((dq,), state, g, last, finish)
            return _

        return lax.fori_loop(0, Lq // blk_q, q_block, _, unroll=unrolled)

    lax.fori_loop(0, n_here, head, None)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *state, scale, causal, blk_q, blk_k, unrolled, heads):
    Lk, W = k_ref.shape[1], heads.lanes
    nqb = q_ref.shape[1] // blk_q
    col_base = lax.mul(pl.program_id(2), Lk)
    row_base = lax.mul(pl.program_id(3), q_ref.shape[1])
    first, last = pl.program_id(3) == 0, pl.program_id(3) == pl.num_programs(3) - 1
    n_here = _heads_here(heads)

    def head(g, _):
        lanes, h, real = _head_in_step(g, n_here, heads)

        def kv_block(kb, _):
            cols = _span(kb, blk_k)
            col0 = lax.add(col_base, lax.mul(kb, blk_k))
            k = _head_lanes(_scaled(k_ref[0, cols, lanes], scale), h, heads)
            v = _head_lanes(v_ref[0, cols, lanes], h, heads)
            carry = _resume((jnp.zeros((blk_k, W), jnp.float32), jnp.zeros((blk_k, W), jnp.float32)), state, g, first)

            def step(masked):
                def q_block(qb, carry):
                    dk, dv = carry
                    rows = _span(qb, blk_q)
                    q = _real_lanes(q_ref[0, rows, lanes], real)
                    do = _real_lanes(do_ref[0, rows, lanes], real)
                    # the block transposed, [blk_k, blk_q]: a query's statistic is a lane's
                    s = lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
                    if masked:
                        row0 = lax.add(row_base, lax.mul(qb, blk_q))
                        s = jnp.where(_below_diagonal(row0, col0, s.shape, 1), s, NEG_INF)
                    p = jnp.exp(s - lse_ref[0, g, :, rows])
                    dv = dv + lax.dot(p.astype(do.dtype), do, preferred_element_type=jnp.float32)
                    dp = lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
                    ds = (p * (dp - delta_ref[0, g, :, rows])).astype(q.dtype)
                    return dk + lax.dot(ds, q, preferred_element_type=jnp.float32), dv

                return q_block

            # query blocks wholly before the block's first key see none of it; then those the diagonal crosses, then the rest
            n_none, n_masked = _diagonal_blocks(lax.sub(col0, row_base), blk_k, blk_q, nqb) if causal else (0, 0)
            dk, dv = _walk(nqb, n_none, n_masked, nqb, True, step, carry, unrolled)

            def finish():
                _store_head(dk_ref, cols, lanes, (dk * scale).astype(dk_ref.dtype), h, heads)
                _store_head(dv_ref, cols, lanes, dv.astype(dv_ref.dtype), h, heads)

            _leave((dk, dv), state, g, last, finish)
            return _

        return lax.fori_loop(0, Lk // blk_k, kv_block, _, unroll=unrolled)

    lax.fori_loop(0, n_here, head, None)


def _call(kernel, name, operands, stats, out_dtypes, q_outer, state_wide, how: _How):
    """One of the three kernels over ``[B, steps of heads, outer steps,
    walked steps]``. ``operands`` are ``("q" | "k", [B, T, N * D] array)``: the
    side whose rows the operand follows; ``stats`` are the ``[B, N, 1, T]`` row
    statistics it reads (it writes one where it reads none: the forward); the
    results follow the outer side, the queries' where ``q_outer``;
    ``state_wide`` says of each value a streaming walk carries from step to
    step whether it is a tile's lanes wide or one column."""
    scale, causal, blk_q, blk_k, interpret, vmem_budget, unroll_pairs, D = how
    B, T, ND = operands[0][1].shape
    outer_side = "q" if q_outer else "k"
    blk_outer, blk_walked = (blk_q, blk_k) if q_outer else (blk_k, blk_q)
    held_outer = sum(side == outer_side for side, _ in operands) + len(out_dtypes)  # tensors whose outer rows a step holds

    def plan(step_lanes):
        return _plan(
            T, step_lanes, max(1, step_lanes // D), operands[0][1].dtype.itemsize, blk_outer, blk_walked,
            held_outer, len(operands) + len(out_dtypes) - held_outer, vmem_budget,
        )

    itemsize = operands[0][1].dtype.itemsize
    heads, steps = _heads_a_step(
        ND, D, lambda step_lanes: not plan(step_lanes).streams and 2 * (len(operands) + len(out_dtypes)) * T * step_lanes * itemsize <= _STEP_BYTES
    )
    W = heads.tiles * heads.lanes  # a step's lanes
    Lo, Lw, streams = plan(W)

    def outer_map(b, t, i, j):
        return (b, i, t)

    def walked_map(b, t, i, j):
        if causal and streams:
            # a chunk the outer block sees nothing of is not fetched: the map stays on the nearest it needs
            if q_outer:
                j = lax.min(j, lax.div(lax.add(lax.mul(i, Lo), Lo - 1), Lw))
            else:
                j = lax.max(j, lax.div(lax.mul(i, Lo), Lw))
        return (b, j, t)

    def stat_map(b, t, i, j):
        return (b, t, 0, (outer_map if q_outer else walked_map)(b, t, i, j)[1])

    stat_spec = pl.BlockSpec((1, heads.a_step, 1, Lo if q_outer else Lw), stat_map)
    in_specs = [
        pl.BlockSpec((1, Lo, W), outer_map) if side == outer_side else pl.BlockSpec((1, Lw, W), walked_map)
        for side, _ in operands
    ] + [stat_spec] * len(stats)
    out_specs = [pl.BlockSpec((1, Lo, W), outer_map) for _ in out_dtypes]
    out_shape = [jax.ShapeDtypeStruct((B, T, ND), dtype) for dtype in out_dtypes]
    if not stats:
        out_specs.append(stat_spec)
        out_shape.append(jax.ShapeDtypeStruct((B, ND // D, 1, T), jnp.float32))
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_budget + _VMEM_INTERMEDIATES,
        )
    return pl.pallas_call(
        functools.partial(
            kernel, scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
            # only a whole sequence a step has constant block indices to unroll over
            unrolled=Lo == Lw == T and (T // blk_outer) * (T // blk_walked) <= unroll_pairs,
            heads=heads,
        ),
        grid=(B, steps, T // Lo, T // Lw),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads.a_step, Lo, heads.lanes if wide else 1), jnp.float32) for wide in state_wide] if streams else [],
        interpret=interpret,
        name=name,
        **params,
    )(*(x for _, x in operands), *stats)


def _flash_fwd(q, k, v, how):
    return _call(_fwd_kernel, "flash_fwd", [("q", q), ("k", k), ("k", v)], [], [q.dtype], True, (False, False, True), how)


def _delta_kernel(do_ref, o_ref, delta_ref, *, heads):
    """``delta[g, t] = sum over head g's lanes of do[t] * o[t]``, float32. The
    matrix unit sums (an indicator row a head against the product, contracted
    over the lanes), which leaves a head's sums along lanes: the row statistic
    the two backward kernels read, with no transpose."""
    prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
    if heads.total is not None:
        prod = _lanes_of(prod, 0, lax.mul(_heads_here(heads), heads.dim))
    shape = (-(-heads.a_step // 8) * 8, prod.shape[1])  # whole sublane tiles of indicator rows
    head = lax.broadcasted_iota(jnp.int32, shape, 0)
    mine = _lanes_of(jnp.ones(shape, jnp.float32), lax.mul(head, heads.dim), lax.mul(lax.add(head, 1), heads.dim))
    sums = lax.dot_general(mine, prod, _NT, precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
    for g in range(heads.a_step):
        delta_ref[0, g] = sums[g : g + 1, :]


def _flash_delta(do, o, how: _How):
    """The backward's second row statistic, ``[B, N, 1, T]`` as ``lse`` is. A
    kernel of its own and not a reduction of XLA's: that one wants the summed
    lanes off the minor dimension, takes ``do`` and the saved ``o`` in a
    layout with ``T`` minor for it, and the backward kernels then get a copy
    of each (compiled for a v5e: two of the copies this layout exists to avoid)."""
    B, T, ND = do.shape

    def rows_of(step_lanes):  # the whole sequence where two operands' two buffers of it and the float32 product fit
        held = T * -(-step_lanes // _LANES) * _LANES * (2 * 2 * do.dtype.itemsize + 2 * 4)
        return T if held <= how.vmem_budget // 2 else how.blk_q

    heads, steps = _heads_a_step(ND, how.head_dim, lambda step_lanes: rows_of(step_lanes) == T)
    W = heads.tiles * heads.lanes
    rows = rows_of(W)
    operand = pl.BlockSpec((1, rows, W), lambda b, t, i: (b, i, t))
    return pl.pallas_call(
        functools.partial(_delta_kernel, heads=heads),
        grid=(B, steps, T // rows),
        in_specs=[operand, operand],
        out_specs=pl.BlockSpec((1, heads.a_step, 1, rows), lambda b, t, i: (b, t, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, ND // how.head_dim, 1, T), jnp.float32),
        interpret=how.interpret,
        name="flash_bwd_delta",
        **({} if how.interpret else {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=how.vmem_budget + _VMEM_INTERMEDIATES)}),
    )(do, o)


def _flash_bwd(how, res, do):
    q, k, v, o, lse = res
    delta = _flash_delta(do, o, how)
    operands = [("q", q), ("k", k), ("k", v), ("q", do)]
    (dq,) = _call(_dq_kernel, "flash_bwd_dq", operands, [lse, delta], [q.dtype], True, (True,), how)
    dk, dv = _call(_dkv_kernel, "flash_bwd_dkv", operands, [lse, delta], [k.dtype, v.dtype], False, (True, True), how)
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

    The kernels read q, k, v and write o where the model's projections leave
    and take them: ``[B, T, N * D]``, of which ``[B, T, N, D]`` is a free
    reshape, tiles of lanes a grid step (``operand_layout``: two heads of 64 a
    tile, a head of 128), so no head-major copy stands around the calls, forward or
    backward, and the residuals are saved as they came. Only a head width
    that neither divides a lane tile nor is divided by one is transposed to
    ``[B * N, T, D]`` at the door.

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

    if operand_layout(N, D).path == "lanes":
        enter = lambda x: x.reshape(B, padded_T, N * D)
        leave = lambda o: o.reshape(B, padded_T, N, D)
    else:  # every head an operand row of its own: [B * N, T, 1 * D]
        enter = lambda x: x.transpose(0, 2, 1, 3).reshape(B * N, padded_T, D)
        leave = lambda o: o.reshape(B, N, padded_T, D).transpose(0, 2, 1, 3)
    how = _How(float(scale), causal, blk_q, blk_k, interpret, vmem_budget, unroll_pairs, D)
    o = leave(_flash_core(enter(q), enter(k), enter(v), how))
    if pad:
        o = o[:, :T]
    return o
