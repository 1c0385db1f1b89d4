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
for keys and values a head. The kernel shares that kernel's walk
(``decode_attention._ragged_kernel``: a grid step a row, the row's live pages
fetched by the kernel's own DMAs into one half of a double buffer while the
other half is attended, a row's first pages fetched by the step before its
own, rolled loops over halves, query tiles and key tiles) and its tile rule
(``_ragged_tiles``, with one kv head of ``Hg = NH`` grouped queries), in a
body of its own: the accepted kernel's file, and so the programs of every
model without a latent layer, are untouched by it. The ``pallas_call`` is
named ``latent_paged_attention``: a profiler trace finds the kernel by it.
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
from deepspeed_tpu.ops.transformer.decode_attention import NEG_INF, _pages_in_stack, _ragged_tiles


def _latent_kernel(pt_ref, len_ref, qlen_ref, x_ref, _pool_in, o_ref, pool, buf, m_s, l_s, acc_s,
                   fetch_sem, write_sem, slot_s, *, scale, P, C, CK, TQ, Hg, W):
    """Grid step ``g`` attends row ``g - 1`` and starts the fetch of row
    ``g``'s first pages (step 0 only fetches). ``x_ref`` ``[W * (Hg + 1),
    lanes]``: the row's queries W-major (slot w of head h at row ``w * Hg +
    h``), then its ``W`` new entries. ``buf`` ``[2, C * P, lanes]`` holds a
    half's pages: scores are taken against all its lanes, values are its
    leading ``o_ref.shape[-1]``. ``pool`` is the whole pool as ``layers * NP``
    pages, read and written in place (``_pool_in`` is the same memory).
    Scalar arithmetic in ``lax`` primitives, as in ``_ragged_kernel``."""
    add, sub, mul, div, lt, gt = lax.add, lax.sub, lax.mul, lax.div, lax.lt, lax.gt
    g = pl.program_id(0)
    R = pl.num_programs(0) - 1
    rows, Dv = o_ref.shape
    TK = CK * P

    def pages_of(row, there):  # where the walk of a row ends: nowhere for a dead one
        walked = lax.bitwise_and(there, gt(qlen_ref[row], 0))
        return lax.select(walked, div(add(len_ref[row], P - 1), P), 0)

    r, nxt = lax.max(sub(g, 1), 0), lax.min(g, R - 1)
    kv_len = len_ref[r]
    start = sub(kv_len, qlen_ref[r])  # the row's write base
    n_pages = pages_of(r, gt(g, 0))
    n_buf = div(add(n_pages, C - 1), C)
    next_pages = pages_of(nxt, lt(g, R))
    n_live = div(add(mul(qlen_ref[r], Hg), TQ - 1), TQ)  # query tiles that hold a real token

    def page_rows(c):
        return pl.ds(pl.multiple_of(mul(c, P), P), P)

    def fetch(row, first, slot, count, wait=False):
        """The copies of ``count`` pages, from table slot ``first`` of ``row``
        on, into half ``slot``: started, or waited for."""

        def page(c, _):
            copy = pltpu.make_async_copy(pool.at[pt_ref[row, add(first, c)]], buf.at[slot, page_rows(c), :], fetch_sem.at[slot])
            copy.wait() if wait else copy.start()
            return _

        lax.fori_loop(0, count, page, None)

    @pl.when(g == 0)
    def _first_step():
        # what a half holds past a row's live pages is masked, and so must be finite
        buf[...] = jnp.zeros_like(buf)
        slot_s[0] = 0

    @pl.when(lt(mul(n_live, TQ), rows))
    def _dead_slots():
        o_ref[...] = jnp.zeros_like(o_ref)

    slot0 = slot_s[0]
    n_halves = lax.max(n_buf, 1)  # a step that attends nothing still fetches for the next

    def half(b, _):
        slot = lax.bitwise_and(add(slot0, b), 1)
        first = mul(b, C)  # the half's first table slot
        base = mul(first, P)  # and its first key's position
        # the next half's pages, the row's own or else the next row's first, are
        # asked for before this half's are waited for: two halves in flight
        own = lt(add(b, 1), n_buf)
        fetch(
            lax.select(own, r, nxt), lax.select(own, add(first, C), 0), sub(1, slot),
            lax.min(lax.select(own, sub(n_pages, add(first, C)), next_pages), C),
        )
        fetch(r, first, slot, lax.min(sub(n_pages, first), C), wait=True)

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

        def query_tile(t, _):
            row0 = mul(t, TQ)
            tile = pl.ds(0 if TQ == rows else pl.multiple_of(row0, TQ), TQ)
            q = x_ref[tile, :]  # [TQ, lanes]
            q_pos = add(div(add(lax.broadcasted_iota(jnp.int32, (TQ, TK), 0), row0), Hg), start)
            # keys the tile's last query sees, counted from the half's first
            seen = sub(lax.min(kv_len, add(add(div(add(row0, TQ - 1), Hg), 1), start)), base)

            def key_tile(kt, carry):
                m, l, acc = carry
                key0 = mul(kt, TK)
                keys = pl.ds(pl.multiple_of(key0, TK), TK)
                s = lax.dot_general(q, buf[slot, keys, :], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)  # [TQ, TK]
                kv_pos = add(lax.broadcasted_iota(jnp.int32, (TQ, TK), 1), add(base, key0))
                live = lax.bitwise_and(lax.le(kv_pos, q_pos), lt(kv_pos, kv_len))
                s = jnp.where(live, mul(s, scale), NEG_INF)
                m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
                corr = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l = corr * l + jnp.sum(p, axis=1, keepdims=True)
                v = buf[slot, keys, :Dv]  # the entries' leading lanes: the same bytes, not fetched again
                acc = acc * corr + lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                return m_new, l, acc

            first_half = b == 0
            m, l, acc = lax.fori_loop(
                0, lax.clamp(0, div(add(seen, TK - 1), TK), C // CK), key_tile,
                (
                    jnp.where(first_half, NEG_INF, m_s[tile, :1]),
                    jnp.where(first_half, 0.0, l_s[tile, :1]),
                    jnp.where(first_half, 0.0, acc_s[tile, :]),
                ),
            )
            m_s[tile, :] = jnp.broadcast_to(m, (TQ, 128))
            l_s[tile, :] = jnp.broadcast_to(l, (TQ, 128))
            acc_s[tile, :] = acc

            @pl.when(b == n_buf - 1)
            def _finish():
                o_ref[tile, :] = (acc / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)

            return _

        if TQ == rows:  # one tile, whatever its height: a static slice
            pl.when(gt(n_pages, 0))(lambda: query_tile(0, None))
        else:
            lax.fori_loop(0, lax.select(gt(n_pages, 0), n_live, 0), query_tile, None)
        # the written pages are on their way since the merge: the half is the
        # next fetch's only once they have left
        lax.fori_loop(c_lo, c_hi, lambda c, _: write_back(c, wait=True), None)
        return _

    lax.fori_loop(0, n_halves, half, None)
    slot_s[0] = lax.bitwise_and(add(slot0, n_halves), 1)  # where the next step finds its first pages


def _latent_by_live_pages(x, pages, lens, qlens, pool, *, scale, Hg, W, Dv, out_dtype, interpret, pages_per_buffer=None):
    """``_latent_kernel`` over ``R + 1`` steps, the pool left where it is."""
    R, _, D = x.shape
    P, maxp = pool.shape[1], pages.shape[1]
    itemsize = jnp.dtype(pool.dtype).itemsize
    C, CK, TQ, _ = _ragged_tiles(1, Hg, W, P, D, maxp, itemsize, pages_per_buffer)
    kernel = functools.partial(_latent_kernel, scale=scale, P=P, C=C, CK=CK, TQ=TQ, Hg=Hg, W=W)
    params = {}
    if not interpret:
        held = (
            2 * C * P * D * itemsize  # the double buffer
            + 2 * W * ((Hg + 1) * D + Hg * Dv) * x.dtype.itemsize  # x and o, twice
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
            pltpu.VMEM((2, C * P, D), pool.dtype),
            pltpu.VMEM(stats, jnp.float32),
            pltpu.VMEM(stats, jnp.float32),
            pltpu.VMEM((W * Hg, Dv), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),  # fetches: a half
            pltpu.SemaphoreType.DMA((1,)),  # write-backs
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((R, W * Hg, Dv), out_dtype), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count from the scalars: the pool is the 5th
        input_output_aliases={4: 1},
        interpret=interpret,
        name="latent_paged_attention",
        **params,
    )(pages, lens, qlens, x, pool)


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
