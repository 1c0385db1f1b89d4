"""Grouped matmul: rows sorted by group, one weight matrix a group.

``grouped_matmul(x [M, K], w [G, K, N], group_sizes [G]) -> [M, N]``: row i
of ``x`` is multiplied by ``w[g]`` (``w[group_offset + g]`` in a longer stack)
where g is the group whose range
``[sum(sizes[:g]), sum(sizes[:g + 1]))`` holds i. The sizes are data: a
shifting split compiles nothing. Rows past ``sum(group_sizes)`` belong to no
group and their output is UNDEFINED (the caller masks them): that is what
lets dead slots of a serving window cost no expert work.

Two implementations, as for the attention kernels (``impl="auto"``: the
kernel on a TPU, XLA elsewhere; ``pallas_interpret`` runs the kernel in
Pallas's interpreter, for tests off a TPU):

* ``xla``: ``jax.lax.ragged_dot``. On the TPU the compiler lowers it to a
  Mosaic kernel of its own (tiles 128 x 512 x 512), whose custom call opens
  with five ``s32`` metadata operands;
* ``pallas``: the kernel below. A grid step is one *visit*: one tile of
  ``tm`` rows meeting one group that owns rows of it, so a group's weights
  are read once for every row tile it touches (once, when ``M <= tm``), and
  never for a group without rows. At most ``M / tm + G - 1`` visits exist;
  the grid has that many, and the dead ones (past the visits the sizes
  make) skip their body and fetch nothing: Pallas copies a block only when
  its index differs from the step before, so a dead visit repeats the last
  live visit's group and row tile AND, where K is tiled, holds the last K
  block that visit left in VMEM instead of walking K again (until PR 37 it
  walked, and so re-read its expert's whole matrix: PERF.md). A row tile's
  ``[tm, K]`` rows are one block, indexed by the tile alone and sliced by
  the K step in the body, so they are read once while the tile's visits
  pass, not once a K step of each. Weight blocks are as large as the budget
  allows (the whole ``[K, N]`` matrix of an expert when it is at most
  4 MiB): a grid step costs ~0.35 us whatever it moves (PERF.md, PR 24), so
  a 4 MiB block keeps that under a tenth of its 5 us of HBM time. All visit
  metadata rides in ONE packed ``s32`` scalar-prefetch operand.

The backward pass of either is ``ragged_dot``'s own (``custom_vjp``): the
kernel is a forward kernel.
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator import on_tpu

ROW_TILE = 128
WEIGHT_BLOCK_BYTES = 4 << 20  # one weight block; two are in flight


def _largest_tile(dim: int, limit: int) -> int:
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``limit``; ``dim`` itself where there is none (a full dimension is
    always a legal block)."""
    if dim <= limit:
        return dim
    for t in range(limit - limit % 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def _tiles(m: int, k: int, n: int, itemsize: int):
    tm = ROW_TILE if m >= ROW_TILE else -(-m // 8) * 8
    tk = _largest_tile(k, 2048)
    tn = _largest_tile(n, max(128, WEIGHT_BLOCK_BYTES // (tk * itemsize)))
    return tm, tk, tn


def _visits(group_sizes, group_offset, tiles_m: int, tm: int):
    """The packed metadata, ``[4 V + 1]`` int32: for each of the
    ``V = tiles_m + G - 1`` grid visits the index of its weight matrix
    (``group_offset`` + its group), its row tile and its group's first and
    end row; then the number of live visits."""
    G = group_sizes.shape[0]
    V = tiles_m + G - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tm - first_tile + 1, 0)
    visit_end = jnp.cumsum(n_tiles)
    live_visits = visit_end[-1]
    v = jnp.minimum(jnp.arange(V, dtype=jnp.int32), jnp.maximum(live_visits - 1, 0))  # dead visits repeat the last live one
    group = jnp.minimum(jnp.searchsorted(visit_end, v, side="right").astype(jnp.int32), G - 1)
    tile = jnp.minimum(first_tile[group] + v - (visit_end[group] - n_tiles[group]), tiles_m - 1)
    return jnp.concatenate([group + group_offset, tile, starts[group], ends[group], live_visits[None]]).astype(jnp.int32)


# The grid's index maps, of a step ``(n, v, kk)`` and the packed metadata: what
# they return is ALL that decides what a step fetches (a block is copied when
# its index differs from the step before), which
# tests/unit/moe/test_grouped_matmul_fetches.py counts on the CPU.


def _x_index(n, v, kk, meta, *, V: int, k_tiles: int):
    """The visit's row tile, whole in K: the same block while the tile's visits pass."""
    return meta[V + v], 0


def _w_index(n, v, kk, meta, *, V: int, k_tiles: int, transposed: bool = False):
    """The visit's matrix and the step's K block; a dead visit stays on the
    last K block, which the last live step left in VMEM."""
    if k_tiles > 1:
        kk = jnp.where(v < meta[4 * V], kk, k_tiles - 1)
    return (meta[v], n, kk) if transposed else (meta[v], kk, n)


def _o_index(n, v, kk, meta, *, V: int, k_tiles: int):
    return meta[V + v], n


def _kernel(meta, x_ref, w_ref, o_ref, acc_ref, *, V: int, tm: int, tk: int, k_tiles: int, transposed: bool = False):
    v, kk = pl.program_id(1), pl.program_id(2)
    tile = meta[V + v]

    @pl.when(v < meta[4 * V])
    def _():
        @pl.when(kk == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...] if k_tiles == 1 else x_ref[:, pl.ds(pl.multiple_of(kk * tk, tk), tk)]
        if transposed:  # the block is [tn, tk]: x w^T, the contraction over both operands' lanes
            acc_ref[...] += jax.lax.dot_general(x, w_ref[0], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        else:
            acc_ref[...] += jnp.dot(x, w_ref[0], preferred_element_type=jnp.float32)

        @pl.when(kk == k_tiles - 1)
        def _():
            rows = tile * tm + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
            mine = (rows >= meta[2 * V + v]) & (rows < meta[3 * V + v])
            # the first visit of a row tile starts it from zeros; later
            # visits (other groups) keep what was stored
            fresh = (v == 0) | (meta[V + jnp.maximum(v - 1, 0)] != tile)
            kept = jnp.where(fresh, jnp.zeros_like(acc_ref), o_ref[...].astype(jnp.float32))
            o_ref[...] = jnp.where(mine, acc_ref[...], kept).astype(o_ref.dtype)


def _pallas_forward(x, w, group_sizes, group_offset, out_dtype, interpret: bool, transposed: bool = False):
    M, K = x.shape
    N = w.shape[-2] if transposed else w.shape[-1]
    G = group_sizes.shape[0]
    tm, tk, tn = _tiles(M, K, N, w.dtype.itemsize)
    rows = -(-M // tm) * tm
    if rows != M:
        x = jnp.pad(x, ((0, rows - M), (0, 0)))
    tiles_m, k_tiles = rows // tm, K // tk
    V = tiles_m + G - 1
    meta = _visits(group_sizes, group_offset, tiles_m, tm)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20,
        )
    at = dict(V=V, k_tiles=k_tiles)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(N // tn, V, k_tiles),
        in_specs=[
            pl.BlockSpec((tm, K), functools.partial(_x_index, **at)),
            pl.BlockSpec((1, tn, tk) if transposed else (1, tk, tn), functools.partial(_w_index, transposed=transposed, **at)),
        ],
        out_specs=pl.BlockSpec((tm, tn), functools.partial(_o_index, **at)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, V=V, tm=tm, tk=tk, k_tiles=k_tiles, transposed=transposed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, N), out_dtype),
        interpret=interpret,
        name="moe_grouped_matmul",
        **params,
    )(meta, x, w)
    return out[:M]


def _xla_forward(x, w, group_sizes, group_offset, out_dtype, transposed: bool = False):
    w = jax.lax.dynamic_slice_in_dim(w, group_offset, group_sizes.shape[0], axis=0)
    if transposed:
        w = jnp.swapaxes(w, 1, 2)
    return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32), preferred_element_type=out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _pallas_matmul(x, w, group_sizes, group_offset, out_dtype, interpret, transposed):
    return _pallas_forward(x, w, group_sizes, group_offset, out_dtype, interpret, transposed)


def _pallas_fwd(x, w, group_sizes, group_offset, out_dtype, interpret, transposed):
    return _pallas_forward(x, w, group_sizes, group_offset, out_dtype, interpret, transposed), (x, w, group_sizes, group_offset)


def _pallas_bwd(out_dtype, interpret, transposed, saved, g):
    if transposed:  # the serving path's form: no model that trains keeps a stack by its output rows
        raise NotImplementedError("grouped_matmul(transposed=True) has no gradient through the kernel; impl='xla' differentiates")
    x, w, group_sizes, group_offset = saved
    _, vjp = jax.vjp(lambda x_, w_: _xla_forward(x_, w_, group_sizes, group_offset, out_dtype), x, w)
    return (*vjp(g), None, None)


_pallas_matmul.defvjp(_pallas_fwd, _pallas_bwd)


def grouped_matmul(x, w, group_sizes, *, group_offset=0, out_dtype=None, impl: str = "auto", transposed: bool = False):
    """See the module docstring. ``w`` may hold more matrices than there are
    groups (``[L * G, K, N]``: every layer's experts, seen as one stack):
    group g then uses ``w[group_offset + g]``, the offset being data, so the
    kernel reads a layer's experts where they lie and nothing slices the
    stack first. ``x`` and ``w`` are multiplied in ``w``'s dtype and
    accumulated in float32; ``out_dtype`` defaults to ``x``'s.
    ``transposed``: ``w`` is ``[G, N, K]``, a matrix stored by its OUTPUT
    rows, and the product is ``x w[g]^T``: for an ``N`` that is no whole
    number of lane tiles (1,856), whose ``[K, N]`` array the device keeps
    K-minor, so that the kernel's operand would be a transposed copy of the
    whole stack, every call (PERF.md section 6, PR 59). Forward only through
    the kernel: its gradient raises (``impl="xla"`` differentiates)."""
    out_dtype = jnp.dtype(out_dtype or x.dtype)
    x = x.astype(w.dtype)
    group_offset = jnp.asarray(group_offset, jnp.int32)
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    if impl == "xla":
        return _xla_forward(x, w, group_sizes, group_offset, out_dtype, transposed)
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"grouped_matmul impl must be auto, pallas, pallas_interpret or xla, got {impl!r}")
    return _pallas_matmul(x, w, group_sizes, group_offset, out_dtype, impl == "pallas_interpret", transposed)
