"""Ragged paged attention over ONE latent a token (TPU): the absorbed form of
latent attention (``models/hybrid_moe.py``, the ``latent`` kind).

A latent layer keeps ``[c_kv ; k_rope]`` of a token, ``lanes`` numbers, in a
pool ``[layers, NP, P, lanes]`` under the same page table as every other paged
layer. All ``NH`` query heads share it: the "key" is the whole entry, the
"value" its leading ``value_lanes`` (``c_kv``). So a page crosses the bus ONCE
a row a layer, into one buffer that both products read, and the step's new
entries are merged into the pages that receive them and written back once.

``latent_paged_attention`` is the entry: the Pallas kernel on a TPU, XLA's
scatter + gather elsewhere, as ``paged_attention.ragged_paged_attention`` is
for keys and values a head. The kernel walks as that one does
(``decode_attention._ragged_kernel``: a grid step a row, the row's live pages
fetched a half at a time by the kernel's own DMAs while an earlier half is
attended, a row's first pages fetched before its own step, a rolled loop over
halves) in a body of its own: the accepted kernel's file, and so the programs
of every model without a latent layer, are untouched by it. The halves lie in
a ring, and the fetches run ahead of the walk through it, over the ends of
rows. What a half's trip hands the MXU is chosen by the shape, when the kernel
is built (``_latent_tiles``):

* wide (``W * Hg >= 128``: prefill chunks; query tiles of 128 rows fill the
  MXU): that kernel's rolled loops over query tiles and key tiles under its
  tile rule (``_ragged_tiles``, one kv head of ``Hg = NH`` grouped queries),
  a ring of two halves;
* narrow (``W * Hg < 128``: decode rows, 20 query rows a key): the work is
  taking the ENTRIES in, the DMA's and then the MXU's, and every key tile
  costs a chain of latencies (scores, maximum, exponentials, ``p . v``) that
  20 rows do not fill, whatever the tile's size. So a trip is ONE key tile,
  the smallest of ``_NARROW_SIZES`` static sizes that holds the half's live
  pages, straight-line code, the running statistics through scratch once a
  half; and the halves are long and three in the ring, so that the DMA queue
  never runs dry while a tile is attended.

The form is a property of the program, not of the step: every latent layer of
a serving step ``paged_ragged_r<rows>_w1`` (decode) runs the narrow form, of
``_w128`` (a prefill chunk of 128 x 20 query rows) the wide one, and a verify
window of up to 6 tokens x 20 heads the narrow one again. The ``pallas_call``
is named ``latent_paged_attention`` in both: a profiler trace finds the
kernel by it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.accelerator import on_tpu
from deepspeed_tpu.ops.transformer.decode_attention import NEG_INF, _TILE_ROWS, _pages_in_stack, _ragged_tiles

# The narrow form: the keys a half holds, how many sizes of key tile it is attended in, and the halves in the ring.
# By ``tools/ragged_kernel_bench.py --models glm47 --set ...`` on a v5e (PR 43; ``decode64_long``: 64 decode rows of
# 1,661 tokens in the mean, 153.0 us by their bytes; us a call): as here 210.6-211.7, where the walk's DMAs alone take
# ~210 and the parent's double buffer of 384 keys took 328.7. Halves of 384 / 768 / 1,024 / 2,048 keys: 296.8 / 235.2 /
# 217.5 / 211.6 (a key tile costs ~0.27 us of chained latencies whatever its size, so few and long ones). One / three
# sizes: 220.1 / 209.3 (a third size is one more traced body a call site: +6% on the cell's ``setup_s`` in PR 42's
# runs). A ring of two / four: 241.1 (the DMA queue runs dry every trip) / 211.8.
_NARROW_HALF_KEYS = 1536
_NARROW_SIZES = 2
_NARROW_RING = 3


def _latent_tiles(Hg, W, P, D, maxp, itemsize, pages_per_buffer=None):
    """``(C, CK, TQ, N)``: pages a half holds, pages a key tile spans, query
    rows a score tile holds, halves in the ring. Wide (``W * Hg`` a whole
    query tile at least): ``_ragged_tiles``' values for one kv head of ``Hg``
    grouped queries, and two halves. Narrow: one query tile, long halves in
    a ring of ``_NARROW_RING``, and ``CK`` what the sizes of a trip's ONE key
    tile step by (``CK``, ``2 CK`` ... ``C`` pages)."""
    C, CK, TQ, _ = _ragged_tiles(1, Hg, W, P, D, maxp, itemsize, pages_per_buffer)
    if W * Hg >= _TILE_ROWS:
        return C, CK, TQ, 2
    if pages_per_buffer is None:
        pages_per_buffer = max(1, min(_NARROW_HALF_KEYS // P, maxp))
    CK = -(-pages_per_buffer // _NARROW_SIZES)
    return pages_per_buffer // CK * CK, CK, TQ, _NARROW_RING


def _latent_kernel(pt_ref, len_ref, qlen_ref, x_ref, _pool_in, o_ref, pool, buf, m_s, l_s, acc_s,
                   fetch_sem, write_sem, ring_s, *, scale, P, C, CK, TQ, Hg, W):
    """Grid step ``g`` attends row ``g - 1`` (step 0 only starts the ring's
    first fetches). ``x_ref`` ``[W * (Hg + 1), lanes]``: the row's queries
    W-major (slot w of head h at row ``w * Hg + h``), then its ``W`` new
    entries. ``buf`` ``[N, C * P, lanes]`` is the ring of halves, each a
    half's pages: scores are taken against all its lanes, values are its
    leading ``o_ref.shape[-1]``. ``ring_s``: the place of the next half to
    attend, and the row, table slot and place of the next to fetch. ``pool``
    is the whole pool as ``layers * NP`` pages, read and written in place
    (``_pool_in`` is the same memory). ``W * Hg < _TILE_ROWS`` is the narrow
    form. Scalar arithmetic in ``lax`` primitives, as in ``_ragged_kernel``."""
    add, sub, mul, div, lt, gt = lax.add, lax.sub, lax.mul, lax.div, lax.lt, lax.gt
    g = pl.program_id(0)
    R = pl.num_programs(0) - 1
    rows, Dv = o_ref.shape
    N = buf.shape[0]  # halves in the ring
    TK = CK * P

    def pages_of(row):  # where the walk of a row ends: nowhere for a dead one
        return lax.select(gt(qlen_ref[row], 0), div(add(len_ref[row], P - 1), P), 0)

    r = lax.max(sub(g, 1), 0)
    kv_len = len_ref[r]
    start = sub(kv_len, qlen_ref[r])  # the row's write base
    n_pages = lax.select(gt(g, 0), pages_of(r), 0)
    n_buf = div(add(n_pages, C - 1), C)
    n_live = div(add(mul(qlen_ref[r], Hg), TQ - 1), TQ)  # query tiles that hold a real token

    def page_rows(c):
        return pl.ds(pl.multiple_of(mul(c, P), P), P)

    def fetch(row, first, slot, wait=False):
        """The copies of a half's pages, from table slot ``first`` of ``row``
        on, into place ``slot`` of the ring: started, or waited for."""

        def page(c, _):
            copy = pltpu.make_async_copy(pool.at[pt_ref[row, add(first, c)]], buf.at[slot, page_rows(c), :], fetch_sem.at[slot])
            copy.wait() if wait else copy.start()
            return _

        lax.fori_loop(0, lax.min(sub(pages_of(row), first), C), page, None)

    def after(slot):
        return lax.select(lax.eq(slot, N - 1), 0, add(slot, 1))

    def fetch_next():
        """Start the fetch of the first half in walk order (the rows in order,
        a row's halves in order, none for a dead row) that none was started
        for, into the ring's next place; past the last row, nothing."""
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
        # what a half holds past a row's live pages is masked, and so must be finite
        buf[...] = jnp.zeros_like(buf)
        for i in range(4):
            ring_s[i] = 0
        # step 0 attends nothing: the ring's first halves are on their way while row 0's queries come
        lax.fori_loop(0, N - 1, lambda _, none: fetch_next(), None)

    @pl.when(lt(mul(n_live, TQ), rows))
    def _dead_slots():
        o_ref[...] = jnp.zeros_like(o_ref)

    def half(b, slot):
        first = mul(b, C)  # the half's first table slot
        base = mul(first, P)  # and its first key's position
        # the place the half before this one was attended from is free: ``N - 1``
        # halves, the row's own and then the next rows', are on their way while
        # this one is attended
        fetch_next()
        fetch(r, first, slot, wait=True)

        # pages of this half that receive the row's new positions ``start ..
        # kv_len - 1``: merged here with the window's entries (one-hot, exact),
        # attended from here, written back from here, once
        c_lo = lax.clamp(0, sub(div(start, P), first), C)
        c_hi = lax.clamp(0, sub(n_pages, first), C)

        def write_back(c, wait=False):
            copy = pltpu.make_async_copy(buf.at[slot, page_rows(c), :], pool.at[pt_ref[r, add(first, c)]], write_sem.at[0])
            copy.wait() if wait else copy.start()

        def merge(c, _):
            pos = add(lax.broadcasted_iota(jnp.int32, (P, W), 0), add(base, mul(c, P)))
            w = lax.broadcasted_iota(jnp.int32, (P, W), 1)
            sel = lax.eq(pos, add(w, start))  # [P, W] one-hot: window slot w lands on page row p
            hit = (pos[:, :1] >= start) & (pos[:, :1] < kv_len)
            new = x_ref[W * Hg :, :].astype(buf.dtype)  # [W, lanes]
            if W > 1:
                # one product term a row at most, so exact in the pool's dtype
                new = lax.dot_general(
                    sel.astype(new.dtype), new, (((1,), (0,)), ((), ())),
                    precision=lax.Precision.HIGHEST if new.dtype == jnp.float32 else None,
                    preferred_element_type=jnp.float32,
                ).astype(buf.dtype)
            buf[slot, page_rows(c), :] = jnp.where(hit, new, buf[slot, page_rows(c), :])
            write_back(c)
            return _

        lax.fori_loop(c_lo, c_hi, merge, None)

        def key_tile(q, q_pos, key0, carry):
            """The running ``(m, l, acc)`` of the query rows ``q`` (at
            positions ``q_pos`` ``[TQ, TK]``) after the half's ``TK`` keys
            from ``key0`` on."""
            m, l, acc = carry
            keys = pl.ds(key0, q_pos.shape[1])
            s = lax.dot_general(q, buf[slot, keys, :], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # [TQ, TK]
            kv_pos = add(lax.broadcasted_iota(jnp.int32, q_pos.shape, 1), add(base, key0))
            live = lax.bitwise_and(lax.le(kv_pos, q_pos), lt(kv_pos, kv_len))
            s = jnp.where(live, mul(s, scale), NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = corr * l + jnp.sum(p, axis=1, keepdims=True)
            v = buf[slot, keys, :Dv]  # the entries' leading lanes: the same bytes, not fetched again
            acc = acc * corr + lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return m_new, l, acc

        def attend(tile, walk):
            """The half's keys as ``walk`` takes them, from the statistics the
            halves before it left the query rows ``tile`` to the ones it leaves."""
            first_half = b == 0
            m, l, acc = walk((
                jnp.where(first_half, NEG_INF, m_s[tile, :1]),
                jnp.where(first_half, 0.0, l_s[tile, :1]),
                jnp.where(first_half, 0.0, acc_s[tile, :]),
            ))
            m_s[tile, :] = jnp.broadcast_to(m, (tile.size, 128))
            l_s[tile, :] = jnp.broadcast_to(l, (tile.size, 128))
            acc_s[tile, :] = acc

            @pl.when(b == n_buf - 1)
            def _finish():
                o_ref[tile, :] = (acc / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)

        def q_positions(row0, keys):
            return add(div(add(lax.broadcasted_iota(jnp.int32, (TQ, keys), 0), row0), Hg), start)

        def query_tile(t, _):
            row0 = mul(t, TQ)
            tile = pl.ds(0 if TQ == rows else pl.multiple_of(row0, TQ), TQ)
            q = x_ref[tile, :]  # [TQ, lanes]
            q_pos = q_positions(row0, TK)
            # keys the tile's last query sees, counted from the half's first
            seen = sub(lax.min(kv_len, add(add(div(add(row0, TQ - 1), Hg), 1), start)), base)
            attend(tile, lambda carry: lax.fori_loop(
                0, lax.clamp(0, div(add(seen, TK - 1), TK), C // CK),
                lambda kt, carry: key_tile(q, q_pos, pl.multiple_of(mul(kt, TK), TK), carry), carry,
            ))
            return _

        def whole_tile(pages):
            """The narrow form's trip: the half's leading ``pages`` as ONE key
            tile, whatever the causal mask leaves of it."""
            tile = pl.ds(0, rows)
            attend(tile, functools.partial(key_tile, x_ref[tile, :], q_positions(0, pages * P), 0))

        if rows < _TILE_ROWS:
            # narrow: the smallest of the key tiles' sizes (in pages) that holds the half's live pages
            sizes = tuple(range(CK, C + 1, CK))
            live = lax.min(sub(n_pages, first), C)
            for below, pages in zip((0,) + sizes, sizes):
                pl.when(lax.bitwise_and(gt(live, below), lax.le(live, pages)))(functools.partial(whole_tile, pages))
        elif TQ == rows:  # one tile, whatever its height: a static slice
            query_tile(0, None)
        else:
            lax.fori_loop(0, n_live, query_tile, None)
        # the written pages are on their way since the merge: the half is the
        # next fetch's only once they have left
        lax.fori_loop(c_lo, c_hi, lambda c, _: write_back(c, wait=True), None)
        return after(slot)

    ring_s[0] = lax.fori_loop(0, n_buf, half, ring_s[0])  # where the next row finds its first half


@functools.lru_cache(maxsize=64)
def _latent_call(tiles, R, Hg, W, D, Dv, pool_shape, pool_dtype, x_dtype, out_dtype, scale, interpret):
    """The ``pallas_call`` of ``_latent_kernel`` over ``R + 1`` steps, built
    ONCE a shape: a serving process calls the entry at several sites of its
    programs (the leading layer's and the scanned period's, in the narrow
    and in the wide program), and the call's own ``jit`` traces the kernel's
    body anew for every callable it is handed, though not for one it knows."""
    C, CK, TQ, N = tiles
    P = pool_shape[1]
    kernel = functools.partial(_latent_kernel, scale=scale, P=P, C=C, CK=CK, TQ=TQ, Hg=Hg, W=W)
    params = {}
    if not interpret:
        held = (
            N * C * P * D * pool_dtype.itemsize  # the ring
            + 2 * W * ((Hg + 1) * D + Hg * Dv) * x_dtype.itemsize  # x and o, twice
            + 4 * W * Hg * (2 * 128 + Dv)  # m, l, acc
        )
        params["compiler_params"] = pltpu.CompilerParams(
            # a row's first pages are fetched by the step before its own
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=held + (24 << 20),
        )

    def row_block(g, pt, ln, ql):  # step g attends row g - 1; step 0 only fetches
        return (lax.max(g - 1, 0), 0, 0)

    whole = pl.BlockSpec(memory_space=pl.ANY)
    stats = (W * Hg, 128)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R + 1,),
        in_specs=[pl.BlockSpec((None, W * (Hg + 1), D), row_block), whole],
        out_specs=[pl.BlockSpec((None, W * Hg, Dv), row_block), whole],
        scratch_shapes=[
            pltpu.VMEM((N, C * P, D), pool_dtype),
            pltpu.VMEM(stats, jnp.float32),
            pltpu.VMEM(stats, jnp.float32),
            pltpu.VMEM((W * Hg, Dv), jnp.float32),
            pltpu.SemaphoreType.DMA((N,)),  # fetches: a half
            pltpu.SemaphoreType.DMA((1,)),  # write-backs
            pltpu.SMEM((4,), jnp.int32),  # the ring: the consumer's place; the producer's row, table slot and place
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, W * Hg, Dv), out_dtype), jax.ShapeDtypeStruct(pool_shape, pool_dtype)],
        # operands count from the scalars: the pool is the 5th
        input_output_aliases={4: 1},
        interpret=interpret,
        name="latent_paged_attention",
        **params,
    )


def _latent_by_live_pages(x, pages, lens, qlens, pool, *, scale, Hg, W, Dv, out_dtype, interpret, pages_per_buffer=None):
    """``_latent_kernel`` over ``R + 1`` steps, the pool left where it is."""
    R, _, D = x.shape
    tiles = _latent_tiles(Hg, W, pool.shape[1], D, pages.shape[1], pool.dtype.itemsize, pages_per_buffer)
    call = _latent_call(tiles, R, Hg, W, D, Dv, pool.shape, pool.dtype, x.dtype, jnp.dtype(out_dtype), scale, interpret)
    return call(pages, lens, qlens, x, pool)


def latent_paged_attention(
    q: jnp.ndarray,  # [R, W, NH, Dq]: each row's window of absorbed queries [q~ ; q_rope]
    new: jnp.ndarray,  # [R, W, Dq]: the window's entries [c_kv ; k_rope], not yet in the pool
    pages: jnp.ndarray,  # [L, NP, P, lanes]: every latent layer's pages
    layer,  # int32 scalar: the layer whose pages this call writes and reads
    page_table: jnp.ndarray,  # [R, MAXP] int32 page ids per row
    kv_lens,  # [R] int32 live length INCLUDING this step's tokens
    q_lens,  # [R] int32 real tokens in the row's window (0 = dead row)
    value_lanes: int,  # an entry's leading lanes that are its value (kv_lora_rank)
    scale: float,
    impl: str = "auto",
    interpret: Optional[bool] = None,
    pages_per_buffer: Optional[int] = None,
):
    """Write the window's entries into ``layer``'s pages and attend every row
    causally over its own: ``NH`` query heads over one shared entry a token
    whose first ``value_lanes`` lanes are also the value. The row metadata,
    the page-table conventions (sentinels on the trash page 0), what dead rows
    and window slots past ``q_lens`` give (zeros, finite garbage) and ``impl``
    are ``paged_attention.ragged_paged_attention``'s. A page may be wider than
    ``Dq`` (576 stored at 640 lanes, ``kv_pool.key_lanes``): q and the entries
    are padded with zeros, which leaves every product what it was. The Pallas
    kernel needs ``lanes`` and ``value_lanes`` to be whole 128-lane tiles (a
    page, and a page's value part, that a DMA and a vector load can address).
    The softmax statistics and the accumulator are float32; ``p`` is rounded
    to the pool's type for ``p . v``, as the products with q are taken in it.

    Returns ``(out [R, W, NH, value_lanes], pages)``."""
    R, W, NH, Dq = q.shape
    L, NP, P, D = pages.shape
    assert new.shape == (R, W, Dq) and Dq <= D and value_lanes <= Dq
    if impl == "auto":
        impl = "pallas" if on_tpu() else "xla"
    if D > Dq:
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, D - Dq),))
        new = jnp.pad(new, ((0, 0),) * 2 + ((0, D - Dq),))
    lens = jnp.broadcast_to(jnp.asarray(kv_lens, jnp.int32), (R,))
    qlens = jnp.broadcast_to(jnp.asarray(q_lens, jnp.int32), (R,))
    if impl == "pallas":
        if D % 128 or value_lanes % 128:
            raise NotImplementedError(f"the latent kernel needs pages and values of whole lane tiles: {D} lanes, {value_lanes} of them the value")
        if interpret is None:
            interpret = not on_tpu()
        # one operand a row: the queries W-major, then the window's entries, in a dtype that holds both exactly
        x = jnp.concatenate(
            [q.reshape(R, W * NH, D), new.astype(pages.dtype)], axis=1, dtype=jnp.promote_types(q.dtype, pages.dtype)
        )
        o, pool = _latent_by_live_pages(
            x, _pages_in_stack(layer, page_table, NP), lens, qlens, pages.reshape(L * NP, P, D),
            scale=float(scale), Hg=NH, W=W, Dv=value_lanes, out_dtype=q.dtype, interpret=interpret,
            pages_per_buffer=pages_per_buffer,
        )
        return o.reshape(R, W, NH, value_lanes), pool.reshape(pages.shape)
    if impl != "xla":
        raise ValueError(f"unknown latent attention impl {impl!r}; expected auto|pallas|xla")
    from deepspeed_tpu.ops.transformer.paged_attention import scatter_pages

    maxp = page_table.shape[1]
    offs = jnp.arange(W, dtype=jnp.int32)[None, :]
    q_pos = (lens - qlens)[:, None] + offs
    # the pool as pages of one "head": the shared scatter (slots past a row's real tokens to the trash page 0)
    pages = scatter_pages(pages[:, :, None], layer, new[:, :, None], page_table, q_pos, offs < qlens[:, None])[:, :, 0]
    table = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, NP - 1)
    kv = pages[layer, table].reshape(R, maxp * P, D)
    scores = jnp.einsum("rwhd,rsd->rhws", q, kv).astype(jnp.float32) * scale
    kv_pos = jnp.arange(maxp * P, dtype=jnp.int32)
    mask = (q_pos[:, None, :, None] >= kv_pos) & (kv_pos < lens[:, None, None, None])
    probs = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1).astype(kv.dtype)
    out = jnp.einsum("rhws,rsd->rwhd", probs, kv[..., :value_lanes]).astype(q.dtype)
    return jnp.where((lens > 0)[:, None, None, None], out, 0), pages
