"""The two ways between token order and expert order of a routed layer, at the
cost of the rows that exist.

``routed_ffn`` puts its ``S k`` assignments in expert order
(``moe/route_plan.py``). Of those rows only the first ``sum(counts)`` belong
to a group: a serving chip holds a sixteenth to a half of the experts, and a
mixed step's token tile is two fifths live, so 1 row in 40 of Laguna's tile is
ever multiplied. ``dispatch`` builds the sorted rows and ``combine`` brings
the experts' outputs back to their tokens, gates applied, in two forms
(``plan_path`` says which, from the shape and the backend):

* ``gather``: ``lax.gather`` of all ``S k`` token rows by ``plan.src``; and back
  a gather of all ``S k`` float32 rows by ``plan.dest``, the mask of the
  assignments that were never computed, and the sum of k slabs with
  ``plan.weights``. What ``routed_ffn`` ran until PR 65, what runs off a TPU
  and where the plan is the sorted one, and the reference the kernels are held
  to.
* ``live_rows``: two ``pallas_call``s (``moe_dispatch_rows``,
  ``moe_combine_rows``) that walk the live rows alone, a block of
  ``ROW_BLOCK`` at a time, a block's copy in flight beside the next one's
  work. A DMA cannot take one row out of a tiled array (Mosaic: a slice of the
  second-minor dimension is whole tiles), so a block's rows move through the
  MXU: ``rows = onehot[block, S] tokens[S, H]`` and ``out += onehot[S, block]
  (row_weight out_rows)[block, H]``, the one-hot built from ``plan.src`` in the
  kernel, every product a one or a zero times a bfloat16 (float32 values in
  their three bfloat16 parts, ``route_plan.bf16_parts``), summed in float32:
  the dispatch is a copy, bit for bit, and the combine adds a token's k rows
  in float32, in expert order where the gather form adds them in choice
  order (ONE product over the three parts, one under the other, so that the
  sums stay in the MXU). A product takes ``TOKEN_CHUNK`` tokens, and only the
  chunks that a row of the groups names: a serving window's tile has its live
  tokens in front. Of a last block, the ``SUB_BLOCK``s behind the groups are
  not prepared. Rows behind the groups are never written (``dispatch``: whatever the
  memory held; ``grouped_matmul`` reads no row outside its groups) and never
  read (``combine``). What is not finite is taken out before a product and
  put back as NaN in the rows and tokens it belongs to, so that one token's
  overflow stays that token's as it does through a gather. Each grid step is
  one slab of columns, the float32 accumulator ``[S, columns]`` in VMEM
  (``ACC_BYTES``), so neither call asks for more than the default scoped VMEM.

Both differentiate through ``custom_vjp``s whose backward passes are the
``jnp`` formulas (``moe/layer.py``: dropless training below a token tile on a
TPU).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.moe.route_plan import RoutePlan, bf16_dot, bf16_parts

ROW_BLOCK = 128  # sorted rows a trip: an MXU pass's rows, and ``grouped_matmul``'s row tile
SUB_BLOCK = 32  # rows of a block that are prepared together: those behind the groups are not
TOKEN_CHUNK = 128  # tokens a product; a chunk that no row of the groups names is skipped
ACC_BYTES = 2 << 20  # the combine's float32 accumulator [S, columns] of one grid step


def rows_at(x: jnp.ndarray, index: jnp.ndarray) -> jnp.ndarray:
    """``x[index]`` for a plan's places (``index`` [...] in ``0 .. len(x)``,
    by construction): nothing to wrap and nothing to clamp, which ``x[index]``
    does in two fusions before each gather."""
    return jax.lax.gather(
        x, index[..., None], jax.lax.GatherDimensionNumbers(offset_dims=(index.ndim,), collapsed_slice_dims=(0,), start_index_map=(0,)),
        slice_sizes=(1, x.shape[1]), mode=jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS,
    )


class _Spec(NamedTuple):
    """What a call is built from, once a shape."""

    S: int
    H: int
    rows: int  # S k
    n: int  # the groups
    dtype: str  # the tokens' (dispatch) or the output's (combine)
    interpret: bool


def _geometry(spec: _Spec):
    """(sorted rows padded to whole blocks, columns a grid step)."""
    padded = -(-spec.rows // ROW_BLOCK) * ROW_BLOCK
    limit = max(128, ACC_BYTES // (4 * spec.S))
    columns = next((c for c in range(limit - limit % 128, 0, -128) if spec.H % c == 0), spec.H) if spec.H > limit else spec.H
    return padded, columns


def _finite(x):
    return jnp.abs(x.astype(jnp.float32)) <= jnp.finfo(jnp.float32).max


def _as_column(row):
    """``[1, ROW_BLOCK]`` float32 (a block's lanes) -> ``[ROW_BLOCK, 1]``: the diagonal of its broadcast."""
    at = jax.lax.broadcasted_iota(jnp.int32, (ROW_BLOCK, ROW_BLOCK), 0) == jax.lax.broadcasted_iota(jnp.int32, (ROW_BLOCK, ROW_BLOCK), 1)
    return jnp.sum(jnp.where(at, row, 0.0), axis=1, keepdims=True)


def _block_rows(block):
    return pl.ds(pl.multiple_of(block * ROW_BLOCK, ROW_BLOCK), ROW_BLOCK)


def _any_not(finite):
    """``[rows, columns]`` bool -> ``[rows, 1]``: the rows that hold something not finite."""
    return jnp.max(jnp.where(finite, 0.0, 1.0), axis=1, keepdims=True) > 0


def _live_rows(counts_ref, n: int):
    return jax.lax.fori_loop(0, n, lambda g, total: total + counts_ref[g], jnp.int32(0))


def _flags(bad):
    """A ``[rows, 1]`` bool as a bfloat16 ``[rows, 128]`` operand of the MXU."""
    return jnp.broadcast_to(bad.astype(jnp.float32), (bad.shape[0], 128)).astype(jnp.bfloat16)


def _token_chunks(S: int):
    """(chunks of tokens, tokens a chunk): a product's tokens go a chunk at a time, and a chunk no live row names is skipped."""
    return (S // TOKEN_CHUNK, TOKEN_CHUNK) if S % TOKEN_CHUNK == 0 else (1, S)


def _tokens_named(src_ref, total):
    """One more than the last token a row of the groups names: in a serving
    window's tile the live tokens lie in front, two fifths of it."""
    lane = jax.lax.broadcasted_iota(jnp.int32, src_ref.shape, 1)
    return jnp.max(jnp.where(lane < total, src_ref[...], -1)) + 1


def _dispatch_kernel(counts_ref, tokens_ref, src_ref, rows_ref, parts_s, bad_s, out_s, flag_s, stage_s, sems, *, spec: _Spec):
    _, columns = _geometry(spec)
    chunks, TC = _token_chunks(spec.S)
    column0 = pl.multiple_of(pl.program_id(0) * columns, columns)
    total = _live_rows(counts_ref, spec.n)
    blocks = (total + ROW_BLOCK - 1) // ROW_BLOCK
    named = _tokens_named(src_ref, total)
    for t in range(chunks):

        @pl.when(t * TC < named)
        def _():
            x = tokens_ref[t * TC : (t + 1) * TC, :]
            finite = _finite(x)
            clean = jnp.where(finite, x, jnp.zeros_like(x))
            for i, part in enumerate((clean,) if x.dtype == jnp.bfloat16 else bf16_parts(clean.astype(jnp.float32))):
                parts_s[i, t * TC : (t + 1) * TC, :] = part
            bad_s[t * TC : (t + 1) * TC, :] = _flags(_any_not(finite))

    token = jax.lax.broadcasted_iota(jnp.int32, (ROW_BLOCK, TC), 1).astype(jnp.float32)

    def store(block, slot):
        return pltpu.make_async_copy(stage_s.at[slot], rows_ref.at[_block_rows(block), pl.ds(column0, columns)], sems.at[slot])

    def body(block, carry):
        slot = block % 2

        @pl.when(block >= 2)
        def _():
            store(block - 2, slot).wait()

        src = _as_column(src_ref[:, _block_rows(block)].astype(jnp.float32))

        def of_chunk(t):
            onehot = (token + float(t * TC) == src).astype(jnp.bfloat16)  # [block, chunk], at most a one a row
            rows = sum(bf16_dot(onehot, parts_s[i, t * TC : (t + 1) * TC, :]) for i in range(parts_s.shape[0]))
            return rows, bf16_dot(onehot, bad_s[t * TC : (t + 1) * TC, :])

        rows, flags = of_chunk(0)  # a block has a live row, and that row a token
        if chunks > 1:
            out_s[...], flag_s[...] = rows, flags
            for t in range(1, chunks):

                @pl.when(t * TC < named)
                def _():
                    more, more_flags = of_chunk(t)
                    out_s[...] += more
                    flag_s[...] += more_flags

            rows, flags = out_s[...], flag_s[...]
        stage_s[slot] = jnp.where(flags[:, :1] > 0, jnp.nan, rows).astype(stage_s.dtype)
        store(block, slot).start()
        return carry

    jax.lax.fori_loop(0, blocks, body, 0)
    for back in (2, 1):

        @pl.when(blocks >= back)
        def _():
            store(blocks - back, (blocks - back) % 2).wait()


def _combine_kernel(counts_ref, rows_ref, weight_ref, src_ref, out_ref, acc_s, bad_s, parts_s, flag_s, stage_s, sems, *, spec: _Spec):
    _, columns = _geometry(spec)
    chunks, TC = _token_chunks(spec.S)
    column0 = pl.multiple_of(pl.program_id(0) * columns, columns)
    total = _live_rows(counts_ref, spec.n)
    blocks = (total + ROW_BLOCK - 1) // ROW_BLOCK
    named = _tokens_named(src_ref, total)
    for t in range(chunks):

        @pl.when(t * TC < named)
        def _():
            acc_s[t * TC : (t + 1) * TC, :] = jnp.zeros((TC, columns), jnp.float32)
            bad_s[t * TC : (t + 1) * TC, :] = jnp.zeros((TC, 128), jnp.float32)

    token = jax.lax.broadcasted_iota(jnp.int32, (TC, ROW_BLOCK), 0)

    def fetch(block, slot):
        return pltpu.make_async_copy(rows_ref.at[_block_rows(block), pl.ds(column0, columns)], stage_s.at[slot], sems.at[slot])

    @pl.when(blocks > 0)
    def _():
        fetch(0, 0).start()

    def body(block, carry):
        slot = block % 2

        @pl.when(block + 1 < blocks)
        def _():
            fetch(block + 1, 1 - slot).start()

        fetch(block, slot).wait()
        at = _block_rows(block)
        weight = _as_column(weight_ref[:, at])
        for r0 in range(0, ROW_BLOCK, SUB_BLOCK):  # the rows' float32 outputs, weighted, in their bfloat16 parts one under the other
            here = slice(r0, r0 + SUB_BLOCK)

            @pl.when(block * ROW_BLOCK + r0 < total)
            def _():
                x = stage_s[slot, here, :]
                exists = block * ROW_BLOCK + r0 + jax.lax.broadcasted_iota(jnp.int32, (SUB_BLOCK, 1), 0) < total  # what lies behind is anything
                finite = _finite(x)
                weighted = jnp.where(finite & exists, x, 0.0) * jnp.where(exists, weight[here], 0.0)
                for i, part in enumerate(bf16_parts(weighted)):
                    parts_s[i * ROW_BLOCK + r0 : i * ROW_BLOCK + r0 + SUB_BLOCK, :] = part
                flag_s[here, :] = _flags(exists & _any_not(finite))

            @pl.when(block * ROW_BLOCK + r0 >= total)
            def _():
                for i in range(3):
                    parts_s[i * ROW_BLOCK + r0 : i * ROW_BLOCK + r0 + SUB_BLOCK, :] = jnp.zeros((SUB_BLOCK, columns), jnp.bfloat16)
                flag_s[here, :] = jnp.zeros((SUB_BLOCK, 128), jnp.bfloat16)

        src = src_ref[:, at]
        exists = block * ROW_BLOCK + jax.lax.broadcasted_iota(jnp.int32, (1, ROW_BLOCK), 1) < total
        for t in range(chunks):

            @pl.when(t * TC < named)
            def _():
                onehot = ((token + t * TC == src) & exists).astype(jnp.bfloat16)  # [chunk, block]: a token's rows of this block
                # ONE product over the three parts: the sums stay in the MXU, where three would each write [chunk, columns]
                acc_s[t * TC : (t + 1) * TC, :] += bf16_dot(jnp.concatenate([onehot] * 3, axis=1), parts_s[...])
                bad_s[t * TC : (t + 1) * TC, :] += bf16_dot(onehot, flag_s[...])

        return carry

    jax.lax.fori_loop(0, blocks, body, 0)
    for t in range(chunks):
        here = slice(t * TC, (t + 1) * TC)

        @pl.when(t * TC < named)
        def _():
            out_ref[here, :] = jnp.where(bad_s[here, :1] > 0, jnp.nan, acc_s[here, :]).astype(out_ref.dtype)

        @pl.when(t * TC >= named)
        def _():
            out_ref[here, :] = jnp.zeros((TC, columns), out_ref.dtype)


def _compiler_params(spec: _Spec):
    # no ``vmem_limit_bytes``: both fit the default 16 MiB (``ACC_BYTES``), and a larger claim is taken from the ops AROUND
    # the call (PERF.md section 6, PR 64)
    return {} if spec.interpret else {"compiler_params": pltpu.CompilerParams(dimension_semantics=("arbitrary",))}


@functools.lru_cache(maxsize=64)
def _dispatch_call(spec: _Spec):
    """``(counts [n], tokens [S, H], src [1, padded]) -> rows [padded, H]``, built once a shape (``route_plan._plan_call``).
    ONE ``s32`` operand in front: a call that opens with three is the ragged attention kernel to the benchmark's readers
    (``tests/unit/inference/test_accepted_programs_guard.py``)."""
    padded, columns = _geometry(spec)
    dtype = jnp.dtype(spec.dtype)
    return pl.pallas_call(
        functools.partial(_dispatch_kernel, spec=spec),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(spec.H // columns,),
            in_specs=[pl.BlockSpec((spec.S, columns), lambda c, counts: (0, c)), pl.BlockSpec((1, padded), lambda c, counts: (0, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((1 if dtype == jnp.bfloat16 else 3, spec.S, columns), jnp.bfloat16),  # the tokens, finite, in bfloat16 parts
                pltpu.VMEM((spec.S, 128), jnp.bfloat16),  # the tokens that held something not finite
                pltpu.VMEM((ROW_BLOCK, columns), jnp.float32),  # a block's rows, summed over the chunks of tokens
                pltpu.VMEM((ROW_BLOCK, 128), jnp.float32),  # and which of them took a token that was not finite
                pltpu.VMEM((2, ROW_BLOCK, columns), dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((padded, spec.H), dtype),
        interpret=spec.interpret,
        name="moe_dispatch_rows",
        **_compiler_params(spec),
    )


@functools.lru_cache(maxsize=64)
def _combine_call(spec: _Spec):
    """``(counts [n], out_rows [padded, H] float32, row_weight [1, padded], src [1, padded]) -> out [S, H]``, built once a shape."""
    padded, columns = _geometry(spec)
    return pl.pallas_call(
        functools.partial(_combine_kernel, spec=spec),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(spec.H // columns,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((1, padded), lambda c, counts: (0, 0)),
                pl.BlockSpec((1, padded), lambda c, counts: (0, 0)),
            ],
            out_specs=pl.BlockSpec((spec.S, columns), lambda c, counts: (0, c)),
            scratch_shapes=[
                pltpu.VMEM((spec.S, columns), jnp.float32),
                pltpu.VMEM((spec.S, 128), jnp.float32),  # the tokens a row that is not finite belongs to
                pltpu.VMEM((3 * ROW_BLOCK, columns), jnp.bfloat16),  # a block's weighted rows in bfloat16 parts
                pltpu.VMEM((ROW_BLOCK, 128), jnp.bfloat16),  # and which of them are not finite
                pltpu.VMEM((2, ROW_BLOCK, columns), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((spec.S, spec.H), jnp.dtype(spec.dtype)),
        interpret=spec.interpret,
        name="moe_combine_rows",
        **_compiler_params(spec),
    )


def _padded(x, padded: int):
    """``[rows, ...] -> [padded, ...]`` (nothing where ``S k`` is whole blocks, as at every serving shape)."""
    return x if x.shape[0] == padded else jnp.pad(x, ((0, padded - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def _exists(counts, rows: int):
    return jnp.arange(rows, dtype=jnp.int32) < jnp.sum(counts)


# --- dispatch -------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch_live(tokens, src, counts, spec: _Spec):
    padded, _ = _geometry(spec)
    return _dispatch_call(spec)(counts, tokens, _padded(src, padded).reshape(1, padded))[: spec.rows]


def _dispatch_fwd(tokens, src, counts, spec):
    return _dispatch_live(tokens, src, counts, spec), (src, counts)


def _dispatch_bwd(spec, saved, g):
    src, counts = saved
    g = jnp.where(_exists(counts, spec.rows)[:, None], g, jnp.zeros_like(g))
    return jnp.zeros((spec.S, spec.H), g.dtype).at[src].add(g), None, None


_dispatch_live.defvjp(_dispatch_fwd, _dispatch_bwd)


def _kernel_impl(impl: str, what: str) -> bool:
    """Whether ``impl`` is the kernel in Pallas's interpreter."""
    if impl not in ("live_rows", "pallas_interpret"):
        raise ValueError(f"{what} impl must be live_rows, pallas_interpret or gather, got {impl!r}")
    return impl == "pallas_interpret"


def dispatch(tokens: jnp.ndarray, plan: RoutePlan, *, impl: str) -> jnp.ndarray:
    """``tokens`` [S, H] -> the sorted rows ``[S k, H]``: ``rows[r] =
    tokens[plan.src[r]]``. ``impl`` ``gather``: every row; ``live_rows`` (the
    kernel; ``pallas_interpret`` in Pallas's interpreter): the
    ``sum(plan.counts)`` rows of the groups, ANYTHING behind them."""
    if impl == "gather":
        return rows_at(tokens, plan.src)
    spec = _Spec(tokens.shape[0], tokens.shape[1], plan.src.shape[0], plan.counts.shape[0], jnp.dtype(tokens.dtype).name, _kernel_impl(impl, "dispatch"))
    return _dispatch_live(tokens, plan.src, plan.counts, spec)


# --- combine --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine_live(out_rows, row_weight, src, counts, spec: _Spec):
    padded, _ = _geometry(spec)
    lanes = lambda x: _padded(x, padded).reshape(1, padded)
    return _combine_call(spec)(counts, _padded(out_rows.astype(jnp.float32), padded), lanes(row_weight.astype(jnp.float32)), lanes(src))


def _combine_fwd(out_rows, row_weight, src, counts, spec):
    return _combine_live(out_rows, row_weight, src, counts, spec), (out_rows, row_weight, src, counts)


def _combine_bwd(spec, saved, g):
    out_rows, row_weight, src, counts = saved
    exists = _exists(counts, spec.rows)
    g_rows = jnp.where(exists[:, None], rows_at(g.astype(jnp.float32), src), 0.0)  # each row's token's cotangent
    d_weight = jnp.sum(g_rows * jnp.where(exists[:, None], out_rows.astype(jnp.float32), 0.0), axis=1)
    return (g_rows * jnp.where(exists, row_weight, 0.0)[:, None]).astype(out_rows.dtype), d_weight.astype(row_weight.dtype), None, None


_combine_live.defvjp(_combine_fwd, _combine_bwd)


def combine(out_rows: jnp.ndarray, plan: RoutePlan, dtype, *, masked: bool, impl: str) -> jnp.ndarray:
    """The experts' outputs ``out_rows`` [S k, H] (float32, sorted rows) ->
    ``[S, H]`` in ``dtype``: each token the sum of its rows that belong to a
    group, times their gates, in float32. ``impl`` ``gather``: all ``S k`` rows
    back to ``[k, S, H]`` by ``plan.dest``, those of no group masked where
    there can be any (``masked``: a ``held`` share or ``live`` tokens), k
    slabs summed with ``plan.weights``; ``live_rows`` (``pallas_interpret``):
    ``out[plan.src[r]] += plan.row_weight[r] out_rows[r]`` over the
    ``sum(plan.counts)`` rows of the groups."""
    if impl == "gather":
        per_choice = rows_at(out_rows, plan.dest)  # [k, S, H]
        if masked:
            per_choice = jnp.where(plan.routed[..., None] != 0, per_choice, 0.0)
        return jnp.sum(per_choice * plan.weights[..., None], axis=0).astype(dtype)
    S = plan.dest.shape[1]
    spec = _Spec(S, out_rows.shape[1], plan.src.shape[0], plan.counts.shape[0], jnp.dtype(dtype).name, _kernel_impl(impl, "combine"))
    return _combine_live(out_rows, plan.row_weight, plan.src, plan.counts, spec)
