"""``ops/transformer/sparse_latent_attention.py`` against a dense masked
softmax in plain ``jax.numpy``, ONE shape a form: the decode form (a sort, a
gather of the chosen entries), the chunk form (each query's own selection
under a masked walk of the row's pages, two blocks of keys) and the ring (a
decode row's newest pages, a chunk's whole ring, three laps in). Float32 on
the CPU: the forms and the dense computation differ by the order of their sums
(measured 2e-7); the limit is 2e-5, where one key more or fewer in a selection
or a window differs by 1e-2 or more.

The decode form's KERNEL (``_walk_chosen_pages``: a row's live pages walked
under the selection's mask) runs in the Pallas interpreter against the gather
form AND the dense masked softmax, a case a property, each in float32 and in
bfloat16 (entries of unit variance: the forms differ by ``p``'s rounding and
the order of their sums, measured 4e-3, limit 3e-2), halves of four pages in
a ring of three so that a row's walk wraps the ring and runs over its end,
key tiles of two pages.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import hybrid_moe as hm
from deepspeed_tpu.ops.transformer import sparse_latent_attention as sla

TOL = 2e-5
P, NH, D, C, IH, ID, TOPK = 8, 4, 24, 16, 3, 8, 12
WALK_LANES, WALK_PAGES, WALK_POOL = 32, 12, 60  # a page's lanes, a row's table, the pool's pages
# live keys a row (four rows a case) and what the case is there for
WALK_CASES = {
    "under_topk": (5, 11, 3, 9),  # every live key is chosen: the plain latent layer
    "at_topk": (12, 12, 12, 12),
    "past_topk": (37, 90, 13, 96),  # up to the whole table
    "tie_at_the_kth": (40, 64, 23, 77),  # scores of few values: the lower position wins
    "dead_rows_among_live": (37, 0, 0, 50),
    "sentinel_pages": (20, 37, 9, 64),  # ids below 0 and past the pool behind a row's live pages
    "page_wider_than_the_entry": (33, 70, 12, 41),  # 24 numbers an entry in pages of 32 lanes (576 in 640)
    "last_page_partly_filled": (33, 41, 7, 95),
}


def _dense(q, entries, seen, scale):
    """``q`` [T, NH, D] over ``entries`` [S, D] under ``seen`` [T, S]: [T, NH, C]."""
    s = jnp.where(seen[:, None], jnp.einsum("thd,sd->ths", q, entries) * scale, -jnp.inf)
    return jnp.einsum("ths,sc->thc", jax.nn.softmax(s, axis=-1), entries[:, :C])


def _row(rng, length, maxp, pages, lanes):
    """A row's keys in pages that are not in walk order: (table row, the pool written, the entries)."""
    entries = rng.standard_normal((length, lanes)).astype(np.float32)
    table = np.full(maxp, -1, np.int32)
    table[: -(-length // P)] = pages[: -(-length // P)]
    return table, entries


@pytest.mark.parametrize("width", [1, 5], ids=["decode", "chunk"])
def test_chosen_keys_attention_is_the_dense_masked_softmax(monkeypatch, width):
    monkeypatch.setattr(sla, "BLOCK_KEYS", 16)  # two pages a block: the walks take several trips
    rng = np.random.default_rng(width)
    R, maxp, NPG = 3, 6, 20
    lens = np.asarray([37, 0, 9 if width == 1 else 21], np.int32)  # a long row, a dead one, one with fewer keys than the selection keeps (decode)
    q_lens = np.where(lens > 0, width, 0).astype(np.int32)
    latent, index = np.zeros((2, NPG, P, D), np.float32), np.zeros((2, NPG, P, ID), np.float32)
    table = np.full((R, maxp), -1, np.int32)
    old, old_i = {}, {}
    for r, pages in ((0, [7, 3, 11, 5, 9, 0]), (2, [2, 13, 4, 0, 0, 0])):
        n = int(lens[r] - q_lens[r])  # what the pool holds before the step
        table[r], old[r] = _row(rng, max(n, 1), maxp, np.asarray(pages), D)
        table[r, : -(-int(lens[r]) // P)] = pages[: -(-int(lens[r]) // P)]
        old_i[r] = rng.standard_normal((max(n, 1), ID)).astype(np.float32)
        for pos in range(n):
            latent[1, table[r, pos // P], pos % P], index[1, table[r, pos // P], pos % P] = old[r][pos], old_i[r][pos]
    q = rng.standard_normal((R, width, NH, D)).astype(np.float32)
    qi = rng.standard_normal((R, width, IH, ID)).astype(np.float32)
    wi = rng.standard_normal((R, width, IH)).astype(np.float32)
    new = rng.standard_normal((R, width, D)).astype(np.float32)
    new_i = rng.standard_normal((R, width, ID)).astype(np.float32)
    out, latent2, index2 = jax.jit(lambda *a: sla.sparse_latent_attention(*a, topk=TOPK, value_lanes=C, scale=0.3))(
        q, qi, wi, new, new_i, jnp.asarray(latent), jnp.asarray(index), jnp.int32(1), table, lens, q_lens
    )
    out = np.asarray(out)
    assert np.all(out[1] == 0) and np.all(np.asarray(latent2)[0] == 0)  # a dead row gives zeros; the other layer's pages are untouched
    for r in (0, 2):
        n = int(lens[r] - q_lens[r])
        entries, keys = np.concatenate([old[r][:n], new[r]]), np.concatenate([old_i[r][:n], new_i[r]])
        pos = n + np.arange(width)
        causal = np.arange(int(lens[r]))[None, :] <= pos[:, None]
        seen = hm.chosen_keys(hm.index_scores(jnp.asarray(qi[r]), jnp.asarray(wi[r]), jnp.asarray(keys)), jnp.asarray(causal), TOPK)
        assert int(seen.sum(-1).max()) == min(TOPK, int(lens[r]))  # row 0 and the chunk's row 2 drop keys; the decode's row 2 keeps its 9
        assert np.abs(out[r] - np.asarray(_dense(jnp.asarray(q[r]), jnp.asarray(entries), seen, 0.3))).max() < TOL, r
        # the step's entries and indexer keys are where the page table says
        for j, p in enumerate(pos):
            assert np.array_equal(np.asarray(latent2)[1, table[r, p // P], p % P], new[r, j]) and np.array_equal(np.asarray(index2)[1, table[r, p // P], p % P], new_i[r, j])


@pytest.mark.parametrize("width,lens", [(1, (61, 0, 9)), (16, (64, 0, 16))], ids=["decode", "chunk"])
def test_ring_attention_is_the_dense_window_three_laps_in(width, lens):
    rng = np.random.default_rng(width)
    window, ring, R, SLOTS = 9, 3, 3, 4  # ring = window_ring_pages(9, 8, 16)
    lens = np.asarray(lens, np.int32)
    q_lens = np.where(lens > 0, width, 0).astype(np.int32)
    slots = np.asarray([2, SLOTS, 0], np.int32)  # the dead row's slot is nobody's
    rings = np.zeros((2, 1 + SLOTS * ring, P, D), np.float32)
    past = {}
    for r in (0, 2):
        n = int(lens[r] - q_lens[r])
        past[r] = rng.standard_normal((max(n, 1), D)).astype(np.float32)
        for pos in range(n):  # what earlier steps left: position p in ring page (p // P) % ring of the slot's own
            rings[1, 1 + slots[r] * ring + (pos // P) % ring, pos % P] = past[r][pos]
    q = rng.standard_normal((R, width, NH, D)).astype(np.float32)
    new = rng.standard_normal((R, width, D)).astype(np.float32)
    out, rings2 = jax.jit(lambda *a: sla.ring_latent_attention(*a, window=window, ring=ring, value_lanes=C, scale=0.3))(
        q, new, jnp.asarray(rings), jnp.int32(1), slots, lens, q_lens
    )
    out = np.asarray(out)
    assert np.all(out[1] == 0) and np.all(np.asarray(rings2)[0] == 0)
    for r in (0, 2):
        n = int(lens[r] - q_lens[r])
        entries = np.concatenate([past[r][:n], new[r]])
        pos, at = n + np.arange(width), np.arange(int(lens[r]))
        seen = (at[None, :] <= pos[:, None]) & (at[None, :] > pos[:, None] - window)
        assert np.abs(out[r] - np.asarray(_dense(jnp.asarray(q[r]), jnp.asarray(entries), jnp.asarray(seen), 0.3))).max() < TOL, r
        one_short = (at[None, :] <= pos[:, None]) & (at[None, :] > pos[:, None] - window + 1)
        assert np.abs(out[r] - np.asarray(_dense(jnp.asarray(q[r]), jnp.asarray(entries), jnp.asarray(one_short), 0.3))).max() > 1e-3


@pytest.fixture()
def short_halves(monkeypatch):
    monkeypatch.setattr(sla, "_WALK_HALF_KEYS", 4 * P)
    monkeypatch.setattr(sla, "_WALK_TILE_KEYS", 2 * P)


# one trace a dtype for all the cases: their shapes are the same
_gather_form = jax.jit(lambda q, s, *a: sla._chosen_entries_attention(q, s, *a, TOPK, C, 0.3))
_selection = jax.jit(lambda scores, lens: hm.chosen_keys(scores, jnp.arange(scores.shape[1])[None, :] < lens[:, None], TOPK))
_walk_form = jax.jit(lambda q, m, *a: sla._walk_chosen_pages(q, m, *a, C, 0.3, interpret=True))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(WALK_CASES))
def test_the_walk_kernel_is_the_gather_form_and_the_dense_masked_softmax(short_halves, case, dtype):
    lens = np.asarray(WALK_CASES[case], np.int32)
    rng = np.random.default_rng(sum(map(ord, case)))
    R, S = lens.size, WALK_PAGES * P
    width = D if case == "page_wider_than_the_entry" else WALK_LANES
    pool = np.zeros((2, WALK_POOL, P, WALK_LANES), np.float32)
    pool[..., :width] = rng.standard_normal((2, WALK_POOL, P, width))  # every page holds something: what is not the row's must not be read
    q = np.zeros((R, NH, WALK_LANES), np.float32)
    q[..., :width] = rng.standard_normal((R, NH, width))
    own = 1 + rng.permutation(WALK_POOL - 1)[: R * WALK_PAGES].reshape(R, WALK_PAGES)  # a row's pages, not in walk order
    table = np.where(np.arange(WALK_PAGES)[None, :] < -(-lens[:, None] // P), own, -1).astype(np.int32)
    if case == "sentinel_pages":
        table[:, -2:] = WALK_POOL + 7
    scores = rng.standard_normal((R, S)).astype(np.float32)
    if case == "tie_at_the_kth":
        scores = np.round(scores)  # seven values or so: the k-th largest is shared
    pool, q = jnp.asarray(pool, dtype), jnp.asarray(q, dtype)
    args = (pool, jnp.int32(1), jnp.asarray(table), jnp.asarray(lens))
    gathered = np.asarray(_gather_form(q, scores, *args), np.float32)
    mask = _selection(scores, lens)
    walked = np.asarray(_walk_form(q, mask, *args), np.float32)
    tol = TOL if dtype == "float32" else 3e-2
    held, queries = np.asarray(pool, np.float32), np.asarray(q, np.float32)  # as the dtype rounded them
    mask = np.asarray(mask)
    cut_ties = 0
    for r, n in enumerate(lens):
        if n == 0:
            assert np.all(walked[r] == 0)
            continue
        # the selection by its definition: the first TOPK of a stable sort by falling score, so ties go to the lower position
        chosen = np.zeros(n, bool)
        chosen[np.argsort(-scores[r, :n], kind="stable")[:TOPK]] = True
        assert np.array_equal(mask[r, :n], chosen) and not mask[r, n:].any()
        tied = scores[r, :n] == np.sort(scores[r, :n])[-min(TOPK, n)]
        cut_ties += tied.sum() > (chosen & tied).sum() > 0
        entries = held[1, table[r, np.arange(n) // P], np.arange(n) % P]  # [n, lanes]
        weights = np.exp(np.where(chosen, (queries[r] @ entries.T) * 0.3, -np.inf))  # the dense masked softmax, in numpy: [NH, n]
        dense = (weights / weights.sum(-1, keepdims=True)) @ entries[:, :C]
        assert np.abs(walked[r] - dense).max() < tol, (r, np.abs(walked[r] - dense).max())
        assert np.abs(walked[r] - gathered[r]).max() < tol, (r, np.abs(walked[r] - gathered[r]).max())
    assert cut_ties >= 2 or case != "tie_at_the_kth"  # rows whose k-th score is shared by keys the selection takes and keys it leaves


@pytest.mark.parametrize("pages, on_a_tpu, form", [(8, True, "walk"), (15, True, "walk"), (16, True, "gather"), (8, False, "gather")], ids=["walk", "boundary", "gather", "cpu"])
def test_the_decode_form_is_chosen_from_the_tables_width(monkeypatch, pages, on_a_tpu, form):
    """``WALK_MAX_MULTIPLE x index_topk`` positions at most: the walk; a page more, and off a TPU: the gather."""
    monkeypatch.setattr(sla, "on_tpu", lambda: on_a_tpu)
    monkeypatch.setattr(sla, "WALK_MAX_MULTIPLE", 10)
    assert sla.decode_form(pages * P, TOPK) == {"form": form, "table_positions": pages * P, "index_topk": TOPK}
