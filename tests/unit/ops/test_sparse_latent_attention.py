"""``ops/transformer/sparse_latent_attention.py`` against a dense masked
softmax in plain ``jax.numpy``, ONE shape a form: the decode form (a sort, a
gather of the chosen entries), the chunk form (each query's own selection
under a masked walk of the row's pages, two blocks of keys) and the ring (a
decode row's newest pages, a chunk's whole ring, three laps in). Float32 on
the CPU: the forms and the dense computation differ by the order of their sums
(measured 2e-7); the limit is 2e-5, where one key more or fewer in a selection
or a window differs by 1e-2 or more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import hybrid_moe as hm
from deepspeed_tpu.ops.transformer import sparse_latent_attention as sla

TOL = 2e-5
P, NH, D, C, IH, ID, TOPK = 8, 4, 24, 16, 3, 8, 12


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
