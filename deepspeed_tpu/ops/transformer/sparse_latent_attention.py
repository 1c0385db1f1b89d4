"""Latent attention over CHOSEN keys, and latent attention over a ring: the
two mixers of a model whose full layers attend the ``index_topk`` keys a
learned indexer scores highest and whose window layers keep latents of their
own rank (``models/hybrid_moe.py``, the ``sparse_latent`` and ``window_latent``
kinds). Both in the absorbed form of ``latent_attention.py``: a token is one
entry ``[c_kv ; k_rope]``, the "key" the whole entry, the "value" its leading
``value_lanes``, shared by all query heads.

Plain XLA, under named scopes the benchmark's readers find device time by
(``SCOPES``): no kernel of this repo walks chosen entries or a ring of
latents, and the accepted latent kernel's file, and so every program that
calls it, is untouched. What the forms are built for:

* ``sparse_latent_attention``, one token a row (a decode row): the row's LIVE
  indexer keys are scored block by block of its page table (a loop whose
  count is the longest live row's blocks, so dead pages are not read), the
  exact top ``index_topk`` positions come from one stable sort (no
  approximation; ties go to the lower position) that carries each position's
  place in the pool as its payload, and ONLY the chosen entries
  are gathered out of the latent pages, 1,280 B each: the unchosen latents do
  not cross the bus. The softmax runs over the chosen entries in whatever
  order the sort left them.
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

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import hybrid_moe as hm

NEG_INF = -1e30
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
    if T == 1:
        out = _chosen_entries_attention(q[:, 0], scores[:, 0], latent, layer, page_table, kv_lens, topk, value_lanes, scale)[:, None]
    else:
        at = jnp.arange(scores.shape[-1], dtype=jnp.int32)
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
