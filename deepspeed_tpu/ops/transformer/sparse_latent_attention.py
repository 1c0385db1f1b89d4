"""Latent attention over CHOSEN keys, and latent attention over a ring: the
two mixers of a model whose full layers attend the ``index_topk`` keys a
learned indexer scores highest and whose window layers keep latents of their
own rank (``models/hybrid_moe.py``, the ``sparse_latent`` and ``window_latent``
kinds). Both in the absorbed form of ``latent_attention.py``: a token is one
entry ``[c_kv ; k_rope]``, the "key" the whole entry, the "value" its leading
``value_lanes``, shared by all query heads.

Under named scopes the benchmark's readers find device time by (``SCOPES``);
the accepted latent kernel's file, and so every program that calls it, is
untouched. What the forms are built for:

* ``sparse_latent_attention``, one token a row (a decode row): the row's LIVE
  indexer keys are scored block by block of its page table (a loop whose
  count is the longest live row's blocks, so dead pages are not read). What
  follows takes one of two forms, chosen where the program is built from
  what it can see (``decode_form``: the page table's positions against
  ``index_topk``, the platform, the pool's lanes):

  - ``walk`` (a TPU, a table of at most ``WALK_MAX_MULTIPLE x index_topk``
    positions): the selection is a MASK of the row's keys
    (``hybrid_moe.chosen_keys``: exact, ties to the lower position, 32 counts
    and no sort), and ONE ``pallas_call`` (``sparse_latent_attention``)
    walks the row's live latent pages where they lie, whole pages of 80 KB a
    DMA into a ring of halves fetched ahead over the ends of rows, and
    attends them under the mask with running softmax statistics: a row's 128
    heads are one full query tile, so the unchosen keys' products ride beside
    the walk's bytes, and a page crosses the bus once;
  - ``gather`` (elsewhere, and the tests' reference): the exact top
    ``index_topk`` positions come from one stable sort that carries each
    position's place in the pool as its payload, and ONLY the chosen entries
    are gathered out of the latent pages, 1,280 B each, ~15 ns an entry
    whatever its width: what wins where the chosen are a hundredth of a
    row's keys (the contexts the model is published for), and loses where
    they are a quarter.
* ``sparse_latent_attention``, a chunk of tokens a row (prefill): the
  selection is each QUERY TOKEN's. Scores ``[T, S]`` against the row's live
  indexer keys, the ``index_topk``-th largest a query built bit by bit from
  32 counts and a count for ties (``hybrid_moe.chosen_keys``: exact, and no
  sort of 512 x 16,384 scores; the reference's own rule), then
  a walk of the row's live pages block by block under that mask with running
  softmax statistics: the same mathematics as attending the chosen keys
  alone, at the walk's bandwidth, which 512 queries x 128 heads hide.
* ``ring_latent_attention``: a slot's ring holds position ``p`` in ring page
  ``(p // P) % ring`` (``kv_pool.StateStore``); a step writes its entries
  there and reads the newest pages that hold a query's ``window`` keys (ten
  of sixteen for a decode row, the whole ring for a chunk), each page's
  positions known from the row's length alone.

While a row has at most ``index_topk`` keys the selection is every key and a
sparse layer is the plain latent layer; its indexer keys are written all the
same.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator import on_tpu
from deepspeed_tpu.models import hybrid_moe as hm
from deepspeed_tpu.ops.transformer.decode_attention import NEG_INF, _pages_in_stack

# the named scopes inside the two mixers' own (``hybrid_moe.SCOPES``)
SCOPES = {"index": "sparse_index_scores", "select": "sparse_select", "attend": "sparse_attend", "ring": "ring_latent_attend"}
BLOCK_KEYS = 1024  # keys one trip of a walk takes (whole pages): a chunk's float32 scores are [heads, T, BLOCK_KEYS]


def write_entries(pool, layer, new, pages, positions, valid):
    """``new`` [R, W, D] into ``layer`` of ``pool`` [L, NP, P, D] at
    ``positions`` [R, W] through the rows' page ids ``pages`` [R, MAXP]: a slot
    that is not ``valid`` [R, W], and a sentinel page id, land on the trash
    page 0."""
    _, NP, P, _ = pool.shape
    slot = jnp.clip(positions // P, 0, pages.shape[1] - 1)
    pid = jnp.where(valid, jnp.clip(jnp.take_along_axis(pages, slot, axis=1), 0, NP - 1), 0)
    return pool.at[layer, pid, positions % P, :].set(new.astype(pool.dtype))


def _pages_of(pool, layer, pids):
    """Pages ``pids`` [...] of ``layer`` as ONE gather out of the pool where it lies: [..., P, D]."""
    L, NP, P, D = pool.shape
    return jnp.take(pool.reshape(L * NP, P, D), layer * NP + jnp.clip(pids, 0, NP - 1), axis=0)


def _blocks(page_table, page_size: int, kv_lens=None):
    """A walk of the rows' pages in blocks of ``BLOCK_KEYS`` keys: (the table
    padded with sentinels to whole blocks, pages a block, blocks in all, the
    blocks that hold a live key of the longest row: the walk's trips)."""
    per = max(1, min(BLOCK_KEYS // page_size, page_table.shape[1]))
    n = -(-page_table.shape[1] // per)
    keys = per * page_size
    trips = n if kv_lens is None else jnp.minimum((jnp.max(kv_lens) + keys - 1) // keys, n)
    return jnp.pad(page_table, ((0, 0), (0, n * per - page_table.shape[1])), constant_values=-1), per, n, trips


def paged_index_scores(qi, wi, index, layer, page_table, kv_lens):
    """The indexer's scores of every query against its row's live keys:
    ``qi`` [R, T, IH, ID], ``wi`` [R, T, IH] float32, ``index`` [L, NP, P,
    ID] -> float32 [R, T, S] with ``S`` the table's positions in whole blocks;
    a block past the longest live row is not read and holds ``-inf``."""
    P = index.shape[2]
    table, per, n, trips = _blocks(page_table, P, kv_lens)
    R, T = qi.shape[:2]
    keys = per * P

    def block(b, scores):
        k = _pages_of(index, layer, jax.lax.dynamic_slice_in_dim(table, b * per, per, axis=1)).reshape(R, keys, -1)
        return jax.lax.dynamic_update_slice_in_dim(scores, hm.index_scores(qi, wi, k), b * keys, axis=2)

    return jax.lax.fori_loop(0, trips, block, jnp.full((R, T, n * keys), -jnp.inf, jnp.float32))


def _chosen_entries_attention(q, scores, latent, layer, page_table, kv_lens, topk, value_lanes, scale):
    """One token a row: ``q`` [R, NH, D] over the ``topk`` entries of largest ``scores`` [R, S] among the row's ``kv_lens`` live ones."""
    L, NP, P, D = latent.shape
    S = scores.shape[-1]
    at = jnp.arange(S, dtype=jnp.int32)
    with jax.named_scope(SCOPES["select"]):
        # ONE stable sort carries each position's place in the pool beside its score, so the chosen entries' addresses come
        # out of the sort and no table is looked up a chosen key (a gather of 65,536 scalars was 0.67 ms a layer: PERF.md,
        # PR 66); exact, and equal scores keep their order: the lower position first
        table = jnp.clip(_blocks(page_table, P)[0], 0, NP - 1)  # [R, S / P]
        where = layer * NP * P + (table[:, :, None] * P + jnp.arange(P, dtype=jnp.int32)).reshape(table.shape[0], S)  # [R, S]
        least_first, where = jax.lax.sort((jnp.where(at[None, :] < kv_lens[:, None], -scores, jnp.inf), where), dimension=1, is_stable=True, num_keys=1)
        least_first, where = least_first[:, : min(topk, S)], where[:, : min(topk, S)]
        real = least_first < jnp.inf  # fewer live keys than topk: the rest are nobody's
    with jax.named_scope(SCOPES["attend"]):
        entries = jnp.take(latent.reshape(L * NP * P, D), jnp.where(real, where, 0), axis=0)  # [R, topk, D]: only these cross the bus
        s = jnp.einsum("rhd,rkd->rhk", q, entries, preferred_element_type=jnp.float32) * scale
        s = jnp.where(real[:, None, :], s, NEG_INF)
        p = jnp.where(real[:, None, :], jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("rhk,rkc->rhc", p.astype(entries.dtype), entries[..., :value_lanes], preferred_element_type=jnp.float32)
        return (o / jnp.where(l == 0, 1.0, l)).astype(q.dtype)


# The walk form: the keys a half of the ring holds, the keys one trip of the MXU takes (a key tile), the halves in the ring.
# By ``tools/ragged_kernel_bench.py --models dots3_sparse --set ...`` on a v5e (PR 67; 32 rows of 128 heads, the longest at
# 8,192 live keys and the others down to three quarters of it, 358.5 us by their bytes; us a call of the kernel alone and ns a
# key walked): as here 564 / 2.47, where halves of 1,024 in tiles of 512 took 647 / 2.83 (a key tile is ONE chain, scores,
# maximum, exponentials, ``p . v``, and the rolled loop's back edge drains the MXU: few and long ones), one tile of 2,048 a half
# 572 / 2.50, halves of 4,096 551 / 2.41 (not worth 16 MB of a float32 ring), tiles of 256 3.5-3.6 ns a key; a ring of two
# +10 us; four tiles of 512 in one straight line 576, two of 1,024 as one alone. The scores as ``entries . q^T`` (the keys
# streamed through the MXU past a stationary q) and transposed back: 3.86 for 2.98, the transposes cost more than the weights'
# loads. A whole half's 32 copies started and waited for in a straight line and not a rolled loop: 2.98 -> 2.83.
_WALK_HALF_KEYS = 2048
_WALK_TILE_KEYS = 1024
_WALK_RING = 3
# The walk form where the page table's positions (the longest row the server can hold) are at most this multiple of
# ``index_topk``, the gather form above it: the walk pays a row's LIVE keys, the gather its chosen ones. Same tool, same
# chip: the gather form 1,628 us a call at 2,130 chosen whatever the rows' lengths (399 its sort, then 18.0 ns an entry
# gathered and attended; 14.8 at 1,065 chosen, 22.7 at 4,260), the walk 51 us of counts and 2.47 ns a key, so at
# ``index_topk`` 2,048 they cross at 32 rows of ~19,300 live keys = 9.4 x ``index_topk`` (measured 16,384: 1,604 us before
# the constants above were chosen, 1,350 by their rate, against 1,580).
WALK_MAX_MULTIPLE = 9
_M_FLOOR = -1e29  # the running maximum's floor, above ``NEG_INF``: what the mask took out gets exp(-9e29) = 0 whatever else a tile held


def decode_form(table_positions: int, topk: int) -> Dict[str, Any]:
    """The form a decode row's attention over its chosen keys takes in a
    program whose page table spans ``table_positions`` keys a row, from the
    shapes and the backend alone: ``form`` (``walk`` | ``gather``). What
    ``sparse_latent_attention`` asks, and what an engine records of its
    programs' shapes where it builds them (``sparse_attend.form``: the ops
    have no tracer)."""
    walk = on_tpu() and table_positions <= WALK_MAX_MULTIPLE * topk
    return {"form": "walk" if walk else "gather", "table_positions": int(table_positions), "index_topk": int(topk)}


def _walk_tiles(P: int, maxp: int):
    """``(C, CK, N)``: pages a half of the ring holds (whole key tiles), pages a key tile spans, halves in the ring."""
    CK = max(1, min(_WALK_TILE_KEYS // P, maxp))
    return max(1, min(_WALK_HALF_KEYS // P, maxp) // CK) * CK, CK, _WALK_RING


def _walk_kernel(pt_ref, len_ref, q_ref, mask_ref, pool, o_ref, buf, fetch_sem, ring_s, *, scale, P, C, CK):
    """Grid step ``g`` attends row ``g - 1`` (step 0 only starts the ring's
    first fetches), as ``latent_attention._latent_kernel`` walks: ``q_ref``
    ``[NH, lanes]`` the row's heads, ONE query tile; ``mask_ref`` ``[key tiles,
    CK * P]`` float32, 0 a chosen key and ``NEG_INF`` any other, a key tile a
    sublane row; ``pool`` the whole pool as ``layers * NP`` pages, read where
    it lies; ``buf`` ``[N, C * P, lanes]`` the ring of halves. Scores against
    all of an entry's lanes, values its leading ``o_ref.shape[-1]``.
    ``ring_s``: the place of the next half to attend, and the row, table slot
    and place of the next to fetch. ``len_ref`` is 0 for a dead row, which
    reads nothing. Scalar arithmetic in ``lax`` primitives, as there."""
    add, sub, mul, div, lt = lax.add, lax.sub, lax.mul, lax.div, lax.lt
    g = pl.program_id(0)
    R = pl.num_programs(0) - 1
    NH, Dv = o_ref.shape
    N = buf.shape[0]
    TK = CK * P

    def pages_of(row):
        return div(add(len_ref[row], P - 1), P)

    r = lax.max(sub(g, 1), 0)
    n_pages = lax.select(lax.gt(g, 0), pages_of(r), 0)
    n_buf = div(add(n_pages, C - 1), C)

    def fetch(row, first, slot, wait=False):
        """The copies of a half's pages, from table slot ``first`` of ``row`` on, into place ``slot`` of the ring: started,
        or waited for. A whole half's in a straight line (a rolled loop's trip costs as much as the copy it starts)."""

        def page(c, _=None):
            rows = pl.ds(c * P, P) if isinstance(c, int) else pl.ds(pl.multiple_of(mul(c, P), P), P)
            copy = pltpu.make_async_copy(pool.at[pt_ref[row, add(first, c)]], buf.at[slot, rows, :], fetch_sem.at[slot])
            copy.wait() if wait else copy.start()

        live = lax.min(sub(pages_of(row), first), C)

        @pl.when(lax.eq(live, C))
        def _whole():
            for c in range(C):
                page(c)

        @pl.when(lt(live, C))
        def _part():
            lax.fori_loop(0, live, page, None)

    def after(slot):
        return lax.select(lax.eq(slot, N - 1), 0, add(slot, 1))

    def fetch_next():
        """Start the fetch of the first half in walk order (the rows in order, a row's halves in order, none for a dead
        row) that none was started for, into the ring's next place; past the last row, nothing."""
        row, first = lax.while_loop(
            lambda at: lax.bitwise_and(lt(at[0], R), lax.ge(at[1], pages_of(lax.min(at[0], R - 1)))),
            lambda at: (add(at[0], 1), 0),
            (ring_s[1], ring_s[2]),
        )

        @pl.when(lt(row, R))
        def _start():
            fetch(row, first, ring_s[3])
            ring_s[3] = after(ring_s[3])

        ring_s[1], ring_s[2] = row, add(first, C)

    @pl.when(g == 0)
    def _first_step():
        buf[...] = jnp.zeros_like(buf)  # what a half holds past a row's live pages is masked, and so must be finite
        for i in range(4):
            ring_s[i] = 0
        lax.fori_loop(0, N - 1, lambda _, none: fetch_next(), None)

    def half(b, carry):
        slot, *stats = carry
        first = mul(b, C)
        fetch_next()  # the place the half before this one was attended from is free
        fetch(r, first, slot, wait=True)
        q = q_ref[...]
        # float32 operands take the precision the process asks for (``highest`` in the float32 logits runs); bfloat16
        # ones name theirs, where Mosaic refuses ``highest`` ("Bad lhs type": ``route_plan.bf16_dot``)
        dot = functools.partial(lax.dot_general, precision=None if q.dtype == jnp.float32 else lax.Precision.DEFAULT, preferred_element_type=jnp.float32)

        def key_tile(kt, stats):
            m, l, acc = stats
            keys = pl.ds(pl.multiple_of(mul(kt, TK), TK), TK)
            s = dot(q, buf[slot, keys, :], (((1,), (1,)), ((), ())))  # [NH, TK]
            s = add(mul(s, scale), mask_ref[pl.ds(add(mul(b, C // CK), kt), 1), :])  # the selection: NEG_INF what was not chosen
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = corr * l + jnp.sum(p, axis=1, keepdims=True)
            v = buf[slot, keys, :Dv]  # the entries' leading lanes: the same bytes, not fetched again
            acc = acc * corr + dot(p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
            return m_new, l, acc

        tiles = div(add(lax.min(sub(n_pages, first), C), CK - 1), CK)  # the half's key tiles that hold a key of the row
        return (after(slot), *lax.fori_loop(0, tiles, key_tile, tuple(stats)))

    start = (ring_s[0], jnp.full((NH, 1), _M_FLOOR, jnp.float32), jnp.zeros((NH, 1), jnp.float32), jnp.zeros((NH, Dv), jnp.float32))
    slot, _, l, acc = lax.fori_loop(0, n_buf, half, start)
    ring_s[0] = slot  # where the next row finds its first half
    o_ref[...] = (acc / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)  # a dead row's zeros


@functools.lru_cache(maxsize=16)
def _walk_call(tiles, R, NH, D, Dv, n_tiles, pool_shape, pool_dtype, out_dtype, scale, interpret):
    """The ``pallas_call`` of ``_walk_kernel`` over ``R + 1`` steps, built ONCE a shape (``latent_attention._latent_call``)."""
    C, CK, N = tiles
    P = pool_shape[1]
    params = {}
    if not interpret:
        held = (
            N * C * P * D * pool_dtype.itemsize  # the ring
            + 2 * NH * (D + Dv) * pool_dtype.itemsize + 2 * 4 * n_tiles * CK * P  # q, o and the mask, twice
            + 4 * NH * (2 * 128 + Dv + 3 * CK * P)  # m, l, acc; a key tile's scores
        )
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # a row's first pages are fetched by the step before its own
            vmem_limit_bytes=held + (24 << 20),
        )

    def row_block(g, pt, ln):  # step g attends row g - 1; step 0 only fetches
        return (lax.max(g - 1, 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R + 1,),
        in_specs=[pl.BlockSpec((None, NH, D), row_block), pl.BlockSpec((None, n_tiles, CK * P), row_block), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, NH, Dv), row_block),
        scratch_shapes=[
            pltpu.VMEM((N, C * P, D), pool_dtype),
            pltpu.SemaphoreType.DMA((N,)),  # fetches: a half
            pltpu.SMEM((4,), jnp.int32),  # the ring: the consumer's place; the producer's row, table slot and place
        ],
    )
    return pl.pallas_call(
        functools.partial(_walk_kernel, scale=scale, P=P, C=C, CK=CK),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, NH, Dv), out_dtype),
        interpret=interpret,
        name="sparse_latent_attention",
        **params,
    )


def _walk_chosen_pages(q, mask, latent, layer, page_table, kv_lens, value_lanes, scale, interpret=None):
    """One token a row: ``q`` [R, NH, lanes] over the keys ``mask`` [R, S]
    (bool; the first ``S`` positions of the row's table) names among the row's
    ``kv_lens`` live ones, by the kernel: every live page of the row crosses
    the bus once, whatever the mask keeps of it."""
    L, NP, P, D = latent.shape
    R, NH, _ = q.shape
    maxp = page_table.shape[1]
    tiles = _, CK, _ = _walk_tiles(P, maxp)
    n_tiles = -(-maxp // CK)
    keys = n_tiles * CK * P
    mask = mask[:, :keys] if mask.shape[1] >= keys else jnp.pad(mask, ((0, 0), (0, keys - mask.shape[1])))
    bias = jnp.where(mask, 0.0, NEG_INF).astype(jnp.float32).reshape(R, n_tiles, CK * P)
    if interpret is None:
        interpret = not on_tpu()
    if not interpret and (D % 128 or value_lanes % 128):
        raise NotImplementedError(f"the sparse latent kernel needs pages and values of whole lane tiles: {D} lanes, {value_lanes} of them the value")
    call = _walk_call(tiles, R, NH, D, value_lanes, n_tiles, (L * NP, P, D), latent.dtype, jnp.dtype(q.dtype), float(scale), interpret)
    return call(_pages_in_stack(layer, page_table, NP), kv_lens, q.astype(latent.dtype), bias, latent.reshape(L * NP, P, D))


def _masked_walk_attention(q, mask, latent, layer, page_table, kv_lens, value_lanes, scale):
    """``q`` [R, T, NH, D] over the row's live pages block by block under
    ``mask`` [R, T, S] (the keys a query attends), running softmax statistics
    in float32: [R, T, NH, value_lanes]."""
    P = latent.shape[2]
    table, per, _, trips = _blocks(page_table, P, kv_lens)
    R, T, NH, _ = q.shape
    keys = per * P

    def block(b, carry):
        m, l, acc = carry
        entries = _pages_of(latent, layer, jax.lax.dynamic_slice_in_dim(table, b * per, per, axis=1)).reshape(R, keys, -1)
        seen = jax.lax.dynamic_slice_in_dim(mask, b * keys, keys, axis=2)[:, None]  # [R, 1, T, keys]
        s = jnp.einsum("rthd,rsd->rhts", q, entries, preferred_element_type=jnp.float32) * scale
        m_new = jnp.maximum(m, jnp.max(jnp.where(seen, s, NEG_INF), axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("rhts,rsc->rhtc", p.astype(entries.dtype), entries[..., :value_lanes], preferred_element_type=jnp.float32)
        return m_new, l, acc

    stats = jnp.full((R, NH, T, 1), NEG_INF, jnp.float32), jnp.zeros((R, NH, T, 1), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, trips, block, (*stats, jnp.zeros((R, NH, T, value_lanes), jnp.float32)))
    return (acc / jnp.where(l == 0, 1.0, l)).transpose(0, 2, 1, 3).astype(q.dtype)


def sparse_latent_attention(q, qi, wi, new, new_index, latent, index, layer, page_table, kv_lens, q_lens, *,
                            topk: int, value_lanes: int, scale: float):
    """Write the window's entries ``new`` [R, T, D] and indexer keys
    ``new_index`` [R, T, ID] into ``layer``'s pages of ``latent`` [L, NP, P,
    lanes] and ``index`` [L, NP, P, ID], and attend each query ``q`` [R, T,
    NH, D] (absorbed: against the stored entry) over the ``topk`` causal keys
    of its row that the indexer (``qi`` [R, T, IH, ID], ``wi`` [R, T, IH]
    float32) scores highest; all of them while there are ``topk`` at most.
    The row metadata (``page_table`` [R, MAXP] with sentinels on the trash
    page, ``kv_lens`` INCLUDING this step's tokens, ``q_lens`` real tokens, 0 a
    dead row) as ``latent_paged_attention``'s; a page may be wider than ``D``
    (zeros). Returns ``(out [R, T, NH, value_lanes], latent, index)``; a dead
    row's and a slot's past ``q_lens`` are zeros."""
    R, T, NH, D = q.shape
    lanes = latent.shape[-1]
    if lanes > D:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, lanes - D),))
        new = jnp.pad(new, ((0, 0),) * 2 + ((0, lanes - D),))
    kv_lens = jnp.where(q_lens > 0, kv_lens, 0).astype(jnp.int32)
    offs = jnp.arange(T, dtype=jnp.int32)[None, :]
    q_pos = (kv_lens - q_lens)[:, None] + offs
    valid = offs < q_lens[:, None]
    latent = write_entries(latent, layer, new, page_table, q_pos, valid)
    index = write_entries(index, layer, new_index, page_table, q_pos, valid)
    with jax.named_scope(SCOPES["index"]):
        scores = paged_index_scores(qi, wi, index, layer, page_table, kv_lens)
    at = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    if T == 1 and decode_form(page_table.shape[1] * latent.shape[2], topk)["form"] == "walk":
        with jax.named_scope(SCOPES["select"]):
            mask = hm.chosen_keys(scores[:, 0], at[None, :] < kv_lens[:, None], topk)
        with jax.named_scope(SCOPES["attend"]):
            out = _walk_chosen_pages(q[:, 0], mask, latent, layer, page_table, kv_lens, value_lanes, scale)[:, None]
    elif T == 1:
        out = _chosen_entries_attention(q[:, 0], scores[:, 0], latent, layer, page_table, kv_lens, topk, value_lanes, scale)[:, None]
    else:
        with jax.named_scope(SCOPES["select"]):
            mask = hm.chosen_keys(scores, valid[..., None] & (at <= q_pos[..., None]), topk)
        with jax.named_scope(SCOPES["attend"]):
            out = _masked_walk_attention(q, mask, latent, layer, page_table, kv_lens, value_lanes, scale)
    return jnp.where(valid[..., None, None], out, 0), latent, index


def ring_latent_attention(q, new, rings, layer, slots, kv_lens, q_lens, *, window: int, ring: int, value_lanes: int, scale: float):
    """Write the window's entries ``new`` [R, T, D] into the rows' rings of
    ``layer`` in ``rings`` [L, 1 + slots * ring, P, lanes] (row r owns pages
    ``1 + slots[r] * ring ..``, position ``p`` in ring page ``(p // P) %
    ring``) and attend each query ``q`` [R, T, NH, D] (absorbed) over the
    newest ``window`` keys up to itself. ``kv_lens`` INCLUDING this step's
    tokens; ``q_lens`` 0: a dead row, which writes to the trash page 0.
    Returns ``(out [R, T, NH, value_lanes], rings)``."""
    R, T, NH, D = q.shape
    L, NPR, P, lanes = rings.shape
    if lanes > D:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, lanes - D),))
        new = jnp.pad(new, ((0, 0),) * 2 + ((0, lanes - D),))
    kv_lens = jnp.where(q_lens > 0, kv_lens, 0).astype(jnp.int32)
    offs = jnp.arange(T, dtype=jnp.int32)[None, :]
    q_pos = (kv_lens - q_lens)[:, None] + offs
    valid = offs < q_lens[:, None]
    own = 1 + slots[:, None] * ring  # a row's first ring page; a dead row's slot is nobody's and is never used
    with jax.named_scope(SCOPES["ring"]):
        pid = jnp.where(valid, own + (q_pos // P) % ring, 0)
        rings = rings.at[layer, pid, q_pos % P, :].set(new.astype(rings.dtype))
        # the newest pages that hold the ``T + window - 1`` keys the window's queries see, the row's last page last
        near = min(ring, (T + window - 3) // P + 2) if T + window > 2 else 1
        page = ((kv_lens - 1) // P)[:, None] - (near - 1) + jnp.arange(near, dtype=jnp.int32)[None, :]  # [R, near] page numbers of the row
        held = (page >= 0) & (q_lens > 0)[:, None]
        entries = jnp.take(rings.reshape(L * NPR, P, lanes), jnp.where(held, layer * NPR + own + page % ring, 0), axis=0)
        entries = entries.reshape(R, near * P, lanes)
        kv_pos = (page[:, :, None] * P + jnp.arange(P, dtype=jnp.int32)).reshape(R, 1, near * P)
        seen = valid[..., None] & jnp.repeat(held, P, axis=1)[:, None, :] & (kv_pos <= q_pos[..., None]) & (kv_pos > q_pos[..., None] - window)
        s = jnp.einsum("rthd,rsd->rhts", q, entries, preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen[:, None], s, NEG_INF)
        p = jnp.where(seen[:, None], jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("rhts,rsc->rhtc", p.astype(entries.dtype), entries[..., :value_lanes], preferred_element_type=jnp.float32)
        out = (o / jnp.where(l == 0, 1.0, l)).transpose(0, 2, 1, 3).astype(q.dtype)
    return jnp.where(valid[..., None, None], out, 0), rings
