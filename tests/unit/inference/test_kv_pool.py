"""Block-pool KV cache allocator tests (``inference/kv_pool.py``).

The pool is the serving layer's memory manager: pages must never be
double-booked, the trash page must never circulate, failed growth must be
all-or-nothing, and defrag must move bytes without changing what any
sequence reads back.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_pool import TRASH_PAGE, PagePool, init_paged_cache
from deepspeed_tpu.models import llama_config


def _pool(num_pages=10, page_size=4, max_slots=3, max_seq_len=32):
    cfg = llama_config("tiny", num_layers=2, max_seq_len=max_seq_len)
    return PagePool(
        cfg, num_pages=num_pages, page_size=page_size, max_slots=max_slots,
        max_seq_len=max_seq_len, dtype=jnp.float32,
    )


def test_alloc_free_accounting():
    pool = _pool()
    assert pool.free_pages() == 9  # page 0 reserved
    assert pool.used_pages() == 0
    slot = pool.alloc_slot(6)  # 6 tokens @ page_size 4 -> 2 pages
    assert slot is not None
    assert pool.free_pages() == 7 and pool.used_pages() == 2
    owned = set(int(p) for p in pool.page_table[slot] if p >= 0)
    assert len(owned) == 2 and TRASH_PAGE not in owned
    pool.advance(slot, 6)
    assert pool.live_tokens() == 6
    assert pool.utilization() == pytest.approx(6 / 8)
    returned = pool.free_slot(slot)
    assert returned == 2
    assert pool.free_pages() == 9 and pool.live_tokens() == 0
    assert (pool.page_table[slot] == -1).all()


def test_pages_are_exclusive_across_slots():
    pool = _pool()
    s1 = pool.alloc_slot(8)
    s2 = pool.alloc_slot(8)
    own1 = {int(p) for p in pool.page_table[s1] if p >= 0}
    own2 = {int(p) for p in pool.page_table[s2] if p >= 0}
    assert own1.isdisjoint(own2)
    assert TRASH_PAGE not in own1 | own2


def test_ensure_is_all_or_nothing():
    pool = _pool(num_pages=6, page_size=4)  # 5 allocatable pages
    slot = pool.alloc_slot(16)  # takes 4 pages
    free_before = pool.free_pages()
    assert free_before == 1
    # growing to 28 tokens needs 7 pages total (+3): must fail AND leave the
    # single free page untouched
    assert not pool.ensure(slot, 28)
    assert pool.free_pages() == free_before
    assert pool.ensure(slot, 20)  # +1 page fits
    assert pool.free_pages() == 0


def test_admission_gating():
    pool = _pool(num_pages=6, page_size=4, max_slots=2)
    assert pool.can_admit(8)
    s1 = pool.alloc_slot(16)  # 4 of 5 pages
    assert s1 is not None
    assert not pool.can_admit(8)  # needs 2 pages, 1 free
    assert pool.alloc_slot(8) is None
    assert pool.can_admit(4)  # 1 page fits
    # a slot-exhausted pool refuses even tiny requests
    s2 = pool.alloc_slot(2)
    assert s2 is not None and pool.alloc_slot(1) is None


def test_max_seq_len_is_enforced():
    pool = _pool(max_seq_len=8, page_size=4, num_pages=10)
    slot = pool.alloc_slot(8)
    assert not pool.ensure(slot, 9)
    with pytest.raises(AssertionError):
        pool.advance(slot, 9)


def test_defrag_preserves_contents_and_compacts():
    pool = _pool(num_pages=10, page_size=4)
    s1 = pool.alloc_slot(8)
    s2 = pool.alloc_slot(8)
    # stamp every owned page with a recognizable value
    k = pool.cache.k_pages
    stamps = {}
    for s in (s1, s2):
        for pid in pool.page_table[s]:
            if pid >= 0:
                k = k.at[:, int(pid)].set(float(pid))
                stamps[(s, int(pid))] = float(pid)
    pool.set_cache(k, pool.cache.v_pages)
    # free s1 -> holes below s2's pages; defrag must close them
    pool.free_slot(s1)
    before = {
        i: float(np.asarray(pool.cache.k_pages[0, int(pid), 0, 0, 0]))
        for i, pid in enumerate(pool.page_table[s2]) if pid >= 0
    }
    moves = pool.defrag()
    live = [int(p) for p in pool.page_table[s2] if p >= 0]
    assert sorted(live) == [1, 2]  # densest prefix after the trash page
    after = {
        i: float(np.asarray(pool.cache.k_pages[0, int(pid), 0, 0, 0]))
        for i, pid in enumerate(pool.page_table[s2]) if pid >= 0
    }
    assert after == before  # same bytes visible through the table
    assert moves >= 1
    # free list must cover exactly the non-live, non-trash pages
    assert pool.free_pages() == 9 - 2
    assert pool.defrag() == 0  # already compact


def test_hbm_formula():
    cfg = llama_config("tiny", num_layers=2, max_seq_len=32)
    pool = _pool(num_pages=10, page_size=4)
    cache = init_paged_cache(cfg, num_pages=10, page_size=4, dtype=jnp.float32)
    per_token = 2 * cfg.num_layers * cfg.num_kv_heads * cfg.head_dim * 4
    assert cache.bytes_per_token == per_token
    assert cache.hbm_bytes() == 10 * 4 * per_token
    slot = pool.alloc_slot(6)
    pool.advance(slot, 6)
    # live HBM counts allocated pages (page-granular), not raw tokens
    assert pool.live_hbm_bytes() == 2 * 4 * per_token


def test_rollback_frees_tail_pages():
    """Speculation's rejected-tail contract: rollback shrinks the live
    length and returns exactly the pages past the new length — LIFO, so
    they are the next ones reallocated — without ever touching page 0."""
    pool = _pool(num_pages=10, page_size=4)
    slot = pool.alloc_slot(10)  # 3 pages
    pool.advance(slot, 10)
    free_before = pool.free_pages()
    tail = int(pool.page_table[slot, 2])
    freed = pool.rollback(slot, 5)  # 10 -> 5 tokens: 2 pages suffice
    assert freed == 1
    assert int(pool.seq_lens[slot]) == 5 and int(pool._owned[slot]) == 2
    assert pool.free_pages() == free_before + 1
    assert int(pool.page_table[slot, 2]) == -1
    assert pool._free[-1] == tail and TRASH_PAGE not in pool._free
    # rollback(0) trims pre-reserved pages past the live length, not tokens
    assert pool.rollback(slot, 0) == 0
    pool.ensure(slot, 12)
    assert pool.rollback(slot, 0) == 1  # the speculative over-reserve
    assert int(pool.seq_lens[slot]) == 5 and int(pool._owned[slot]) == 2


def test_rollback_then_advance_roundtrip():
    """advance after rollback must work once pages are re-ensured, and the
    page-boundary case (rollback to an exact multiple) frees nothing."""
    pool = _pool(num_pages=10, page_size=4)
    slot = pool.alloc_slot(8)
    pool.advance(slot, 8)
    assert pool.rollback(slot, 4) == 1  # 8 -> 4: exactly one page back
    assert pool.rollback(slot, 1) == 0  # 4 -> 3: same page still needed
    assert pool.ensure(slot, 9)
    pool.advance(slot, 6)
    assert int(pool.seq_lens[slot]) == 9
    # a full rollback empties the slot but keeps it allocated
    assert pool.rollback(slot, 9) == 3
    assert int(pool.seq_lens[slot]) == 0 and int(pool._owned[slot]) == 0
    assert pool.free_pages() == 9
    with pytest.raises(ValueError, match="rollback"):
        pool.rollback(slot, 1)  # more tokens than the slot holds
    with pytest.raises(ValueError, match="rollback"):
        pool.rollback(slot, -1)


def test_rollback_interacts_with_defrag():
    """Pages freed by rollback become defrag holes; compaction must keep
    every surviving token's bytes visible through the table."""
    pool = _pool(num_pages=10, page_size=4)
    s1 = pool.alloc_slot(8)
    s2 = pool.alloc_slot(8)
    pool.advance(s1, 8)
    pool.advance(s2, 8)
    k = pool.cache.k_pages
    for s in (s1, s2):
        for pid in pool.page_table[s]:
            if pid >= 0:
                k = k.at[:, int(pid)].set(float(pid))
    pool.set_cache(k, pool.cache.v_pages)
    # roll s1 back to one page: its second page becomes a hole below s2
    pool.rollback(s1, 4)
    keep = {
        (s, i): float(np.asarray(pool.cache.k_pages[0, int(pid), 0, 0, 0]))
        for s in (s1, s2)
        for i, pid in enumerate(pool.page_table[s]) if pid >= 0
    }
    pool.defrag()
    live = sorted(
        int(p) for s in (s1, s2) for p in pool.page_table[s] if p >= 0
    )
    assert live == [1, 2, 3]  # densest prefix after the trash page
    after = {
        (s, i): float(np.asarray(pool.cache.k_pages[0, int(pid), 0, 0, 0]))
        for s in (s1, s2)
        for i, pid in enumerate(pool.page_table[s]) if pid >= 0
    }
    assert after == keep
    assert pool.free_pages() == 9 - 3


def test_rows_returns_copies():
    pool = _pool()
    slot = pool.alloc_slot(4)
    pt, lens = pool.rows([slot])
    pt[0, 0] = -7
    assert pool.page_table[slot, 0] != -7


def test_reject_degenerate_pools():
    cfg = llama_config("tiny", num_layers=2, max_seq_len=32)
    with pytest.raises(ValueError, match="reserved"):
        PagePool(cfg, num_pages=1, page_size=4, max_slots=1)


# ---------------------------------------------------------------------------
# prefix sharing: hash-of-block index, refcounts, copy-on-write
# ---------------------------------------------------------------------------
def _prefill_slot(pool, slot, tokens, stamp=None):
    """Test-side stand-in for the scheduler's prefill: write barrier +
    (optional page stamping with recognizable values) + advance + publish
    to the prefix index."""
    tokens = np.asarray(tokens, np.int32)
    n = int(tokens.size)
    cur = int(pool.seq_lens[slot])
    assert pool.prepare_write(slot, n)
    if stamp is not None:
        k = pool.cache.k_pages
        for i in range(pool.pages_for(n)):
            k = k.at[:, int(pool.page_table[slot, i])].set(float(stamp + i))
        pool.set_cache(k, pool.cache.v_pages)
    pool.advance(slot, n - cur)
    pool.register_prefix(slot, tokens)


def _page_val(pool, pid):
    return float(np.asarray(pool.cache.k_pages[0, int(pid), 0, 0, 0]))


def test_prefix_attach_pays_pages_once():
    """The acceptance contract: N requests sharing a prompt prefix hold ONE
    copy of its full pages — refcounts rise, allocation doesn't."""
    pool = _pool(num_pages=20, page_size=4, max_slots=3, max_seq_len=32)
    prompt = np.arange(13, dtype=np.int32)  # 3 full pages + 1 token
    s1 = pool.alloc_slot(14, prefix_tokens=prompt)
    assert int(pool.seq_lens[s1]) == 0  # cold index: nothing attached
    _prefill_slot(pool, s1, prompt)
    assert pool.stats["registered_pages"] == 3
    used_before = pool.used_pages()
    s2 = pool.alloc_slot(14, prefix_tokens=prompt)
    s3 = pool.alloc_slot(14, prefix_tokens=prompt)
    # both attach the 3 shared pages and reserve only their private tail
    for s in (s2, s3):
        assert int(pool.seq_lens[s]) == 12  # 3 pages * 4 tokens attached
        np.testing.assert_array_equal(pool.page_table[s][:3], pool.page_table[s1][:3])
    for pid in pool.page_table[s1][:3]:
        assert int(pool._refcount[int(pid)]) == 3
    # the shared prefix cost zero new pages; each attacher only added its
    # own tail reservation (14 tokens -> 4 pages, 3 shared + 1 fresh)
    assert pool.used_pages() == used_before + 2
    assert pool.stats["prefix_hit_pages"] == 6
    assert pool.stats["prefix_hit_tokens"] == 24
    assert pool.prefix_stats()["prefix_hit_rate"] > 0


def test_prefix_survives_author_and_reattaches_from_cache():
    """Freeing the last reference parks indexed pages on the cached LRU
    (reclaimable, so free_pages counts them) — a later identical prompt
    attaches them instead of re-prefilling."""
    pool = _pool(num_pages=10, page_size=4, max_slots=2, max_seq_len=32)
    prompt = np.arange(9, dtype=np.int32)  # 2 full pages + 1
    s1 = pool.alloc_slot(10, prefix_tokens=prompt)
    _prefill_slot(pool, s1, prompt, stamp=7)
    shared = [int(p) for p in pool.page_table[s1][:2]]
    pool.free_slot(s1)
    assert pool.cached_pages() == 2  # indexed pages outlive their author
    assert pool.free_pages() == 9  # ...but stay reclaimable
    assert pool.used_pages() == 0
    s2 = pool.alloc_slot(10, prefix_tokens=prompt)
    assert int(pool.seq_lens[s2]) == 8
    assert [int(p) for p in pool.page_table[s2][:2]] == shared
    assert _page_val(pool, pool.page_table[s2][0]) == 7.0  # the author's bytes
    assert pool.cached_pages() == 0


def test_cached_pages_evicted_when_free_list_dry():
    """Allocation pressure reclaims cold cached pages (oldest first) and
    drops their index entries — sharing never causes an admission refusal."""
    pool = _pool(num_pages=6, page_size=4, max_slots=2, max_seq_len=32)
    prompt = np.arange(9, dtype=np.int32)
    s1 = pool.alloc_slot(10, prefix_tokens=prompt)  # 3 of 5 pages
    _prefill_slot(pool, s1, prompt)
    pool.free_slot(s1)
    assert pool.cached_pages() == 2 and pool.free_pages() == 5
    # a 17-token stranger needs 5 pages: both cached pages must be evicted
    s2 = pool.alloc_slot(17)
    assert s2 is not None
    assert pool.cached_pages() == 0
    assert pool.stats["cache_evictions"] == 2
    # the index is empty again: the old prompt no longer matches
    assert pool.match_prefix(prompt) == []


def test_cow_on_divergence_preserves_shared_reader():
    """A write into a SHARED page (refcount > 1) must copy, not mutate:
    the writer gets a private duplicate, the other reader and the prefix
    index keep the original bytes."""
    pool = _pool(num_pages=12, page_size=4, max_slots=3, max_seq_len=32)
    prompt = np.arange(9, dtype=np.int32)  # 2 full pages + 1
    s1 = pool.alloc_slot(10, prefix_tokens=prompt)
    _prefill_slot(pool, s1, prompt, stamp=3)  # pages stamped 3.0, 4.0
    s2 = pool.alloc_slot(10, prefix_tokens=prompt)
    orig = [int(p) for p in pool.page_table[s2][:2]]
    assert [int(p) for p in pool.page_table[s1][:2]] == orig
    # s1 diverges: speculative rollback INTO the shared second page, then a
    # re-write of positions 6.. — the write barrier must CoW page index 1
    pool.rollback(s1, 3)  # 9 -> 6 tokens, page 1 still needed
    assert pool.prepare_write(s1, 8)
    assert pool.stats["cow_copies"] == 1
    new_p1 = int(pool.page_table[s1, 1])
    assert new_p1 != orig[1]
    assert int(pool.page_table[s2, 1]) == orig[1]  # reader untouched
    assert int(pool._refcount[orig[1]]) == 1 and int(pool._refcount[new_p1]) == 1
    # the copy carries the original bytes (divergence starts from them)
    assert _page_val(pool, new_p1) == _page_val(pool, orig[1]) == 4.0
    # the index still serves the ORIGINAL page for new matches
    assert [p for p, _ in pool.match_prefix(prompt)] == orig


def test_cow_on_a_pool_of_shared_lanes_copies_every_head_of_the_page():
    """The tiny model's 8 KV heads of 32 lie four to a 128-lane page
    (``heads_per_group``). A page is still one index of axis 1, so the
    copy-on-write duplicate carries all of them: read back THROUGH the
    attention entry at the true heads, the writer's row attends exactly what
    the reader's does, and the grouping survives every reinstall of the
    arrays (the copy, ``set_cache``, ``defrag``)."""
    from deepspeed_tpu.ops.transformer.paged_attention import ragged_paged_attention

    pool = _pool(num_pages=12, page_size=4, max_slots=3, max_seq_len=32)
    cfg = llama_config("tiny", num_layers=2, max_seq_len=32)
    assert pool.cache.heads_per_group == 4 and pool.cache.k_pages.shape == (2, 12, 2, 4, 128)
    prompt = np.arange(9, dtype=np.int32)
    s1 = pool.alloc_slot(10, prefix_tokens=prompt)
    rs = np.random.RandomState(0)
    shape = (9, cfg.num_kv_heads, cfg.head_dim)
    k_new, v_new = jnp.asarray(rs.randn(*shape), jnp.float32), jnp.asarray(rs.randn(*shape), jnp.float32)
    q = jnp.asarray(rs.randn(1, 1, cfg.num_heads, cfg.head_dim), jnp.float32)

    def attend(slot, kv_len, window_k, window_v):
        """The entry on the pool's arrays, layer 1: writes ``window_*`` behind ``kv_len - n`` and attends."""
        n = window_k.shape[0]
        out, k, v = ragged_paged_attention(
            jnp.broadcast_to(q, (1, n) + q.shape[2:]), window_k[None], window_v[None], pool.cache.k_pages, pool.cache.v_pages, 1,
            jnp.asarray(pool.page_table[slot][None]), jnp.asarray([kv_len], jnp.int32), jnp.asarray([n], jnp.int32), impl="xla",
        )
        pool.set_cache(k, v)
        return np.asarray(out[0, n - 1])

    assert pool.prepare_write(s1, 9)
    attend(s1, 9, k_new, v_new)  # the prefill: 9 tokens' keys and values, every head in its own lanes
    pool.advance(s1, 9)
    pool.register_prefix(s1, prompt)
    s2 = pool.alloc_slot(10, prefix_tokens=prompt)  # attaches the two full pages
    orig = [int(p) for p in pool.page_table[s2][:2]]
    pool.rollback(s1, 3)
    assert pool.prepare_write(s1, 8) and pool.stats["cow_copies"] == 1 and pool.cache.heads_per_group == 4
    copy = int(pool.page_table[s1, 1])
    assert copy != orig[1] and int(pool.page_table[s2, 1]) == orig[1]
    for pages in (pool.cache.k_pages, pool.cache.v_pages):
        np.testing.assert_array_equal(np.asarray(pages[:, copy]), np.asarray(pages[:, orig[1]]))
    assert float(jnp.abs(pool.cache.k_pages[1, copy]).min(axis=0).min(axis=0).max()) > 0  # every head's lanes of every group hold keys
    # the writer writes positions 6, 7 again: its query then attends the 8 keys of the prompt, each head its own
    got = attend(s1, 8, k_new[6:8], v_new[6:8])
    group = cfg.num_heads // cfg.num_kv_heads
    for h in range(cfg.num_heads):
        k, v = np.asarray(k_new[:8, h // group]), np.asarray(v_new[:8, h // group])
        scores = k @ np.asarray(q[0, 0, h]) / np.sqrt(cfg.head_dim)
        p = np.exp(scores - scores.max())
        np.testing.assert_allclose(got[h], (p / p.sum()) @ v, rtol=1e-5, atol=1e-5, err_msg=f"head {h}")
    pool.integrity_check()
    pool.defrag()
    assert pool.cache.heads_per_group == 4


def test_write_barrier_invalidates_exclusive_indexed_page():
    """Re-writing an indexed page you own exclusively must drop it from
    the index (an indexed page's content is immutable) — no copy needed."""
    pool = _pool(num_pages=10, page_size=4, max_slots=2, max_seq_len=32)
    prompt = np.arange(9, dtype=np.int32)
    s1 = pool.alloc_slot(10, prefix_tokens=prompt)
    _prefill_slot(pool, s1, prompt)
    assert len(pool.match_prefix(prompt)) == 2
    # mid-page rollback: page 1 stays OWNED (9 -> 6 tokens, 2 pages keep)
    # with its index entry, so the re-write must invalidate in place
    pool.rollback(s1, 3)
    assert pool.prepare_write(s1, 8)  # rewrite positions 6..7
    assert pool.stats["cow_copies"] == 0  # exclusive: no copy
    assert pool.stats["index_invalidations"] == 1
    # page 0's content is untouched (write span starts inside page 1)
    assert len(pool.match_prefix(prompt)) == 1


def test_match_prefix_caps_at_one_token_short():
    """A fully-cached prompt must still leave >= 1 token to prefill (the
    first output token needs logits), so the match is capped."""
    pool = _pool(num_pages=10, page_size=4, max_slots=2, max_seq_len=32)
    prompt = np.arange(8, dtype=np.int32)  # exactly 2 full pages
    s1 = pool.alloc_slot(9, prefix_tokens=prompt)
    _prefill_slot(pool, s1, prompt)
    assert len(pool.match_prefix(prompt)) == 1  # (8 - 1) // 4 = 1 page cap
    longer = np.arange(9, dtype=np.int32)
    assert len(pool.match_prefix(longer)) == 2  # 9 tokens may use both


def test_defrag_remaps_shared_pages_and_index():
    """Defrag with sharing: a page referenced by two tables moves ONCE,
    both tables and the hash index follow, refcounts survive."""
    pool = _pool(num_pages=12, page_size=4, max_slots=3, max_seq_len=32)
    filler = pool.alloc_slot(8)  # occupies low pages, freed later -> holes
    prompt = np.arange(9, dtype=np.int32)
    s1 = pool.alloc_slot(10, prefix_tokens=prompt)
    _prefill_slot(pool, s1, prompt, stamp=5)
    s2 = pool.alloc_slot(10, prefix_tokens=prompt)
    pool.free_slot(filler)
    shared_before = [int(p) for p in pool.page_table[s1][:2]]
    val_before = [_page_val(pool, p) for p in shared_before]
    pool.defrag()
    shared_after = [int(p) for p in pool.page_table[s1][:2]]
    np.testing.assert_array_equal(pool.page_table[s2][:2], shared_after)
    assert [_page_val(pool, p) for p in shared_after] == val_before
    assert all(int(pool._refcount[p]) == 2 for p in shared_after)
    # the index moved with the pages: a fresh match returns the new ids
    assert [p for p, _ in pool.match_prefix(prompt)] == shared_after


# ---------------------------------------------------------------------------
# randomized partition invariant (the CoW/refcount soak)
# ---------------------------------------------------------------------------
def _check_partition(pool):
    """free ∪ cached ∪ referenced exactly partitions pages 1..N-1; the
    refcount array equals the table reference counts; the hash index is a
    bijection onto live pages; per-slot lengths fit their owned pages."""
    N = pool.num_pages
    refs = {}
    for s in range(pool.max_slots):
        owned = int(pool._owned[s])
        row = pool.page_table[s]
        assert (row[owned:] == -1).all(), f"slot {s}: stale entries past owned"
        for i in range(owned):
            p = int(row[i])
            assert p > 0, f"slot {s} references the trash page"
            refs[p] = refs.get(p, 0) + 1
        live = int(pool.seq_lens[s])
        assert live <= owned * pool.page_size
        assert pool.pages_for(live) <= owned
    for p in range(N):
        assert int(pool._refcount[p]) == refs.get(p, 0), f"refcount drift on page {p}"
    fset, cset, rset = set(pool._free), set(pool._cached), set(refs)
    assert len(pool._free) == len(fset), "duplicate free-list entries"
    assert TRASH_PAGE not in fset | cset | rset
    assert fset.isdisjoint(cset) and fset.isdisjoint(rset) and cset.isdisjoint(rset)
    assert fset | cset | rset == set(range(1, N)), "pool partition broken"
    assert set(pool._page_hash) <= cset | rset, "index points at a free page"
    assert cset <= set(pool._page_hash), "cached page without an index entry"
    for page, key in pool._page_hash.items():
        assert pool._hash_index.get(key) == page
    assert len(pool._hash_index) == len(pool._page_hash)


def test_randomized_admit_rollback_preempt_defrag_partition():
    """Soak the allocator with arbitrary admit / attach / prefill / decode
    / rollback / preempt(free) / defrag sequences — heavy prompt reuse so
    attach, CoW, invalidation, caching, and eviction all fire — checking
    the full partition invariant after every operation. Catches exactly
    the refcount leaks a CoW bug would introduce."""
    P = 4
    for seed in (0, 1, 2):
        rs = np.random.RandomState(seed)
        pool = _pool(num_pages=16, page_size=P, max_slots=4, max_seq_len=40)
        # shared corpus: slots draw prompts from few streams -> real sharing
        corpus = [rs.randint(0, 50, (40,)).astype(np.int32) for _ in range(3)]
        slots = {}  # slot -> its context tokens (grows as it "decodes")
        saw = {"cow": False, "attach": False, "evict": False}
        for _ in range(140):
            op = rs.randint(6)
            if op == 0 or not slots:  # admit with a (often shared) prompt
                stream = corpus[rs.randint(len(corpus))]
                n = int(rs.randint(5, 20))
                prompt = stream[:n].copy()
                slot = pool.alloc_slot(n + 1, prefix_tokens=prompt)
                if slot is not None:
                    if int(pool.seq_lens[slot]) > 0:
                        saw["attach"] = True
                    assert pool.prepare_write(slot, n)
                    pool.advance(slot, n - int(pool.seq_lens[slot]))
                    pool.register_prefix(slot, prompt)
                    slots[slot] = prompt
            elif op == 1:  # decode a few tokens (shared continuations)
                slot = list(slots)[rs.randint(len(slots))]
                ctx = slots[slot]
                g = int(rs.randint(1, 6))
                new_len = int(pool.seq_lens[slot]) + g
                if new_len <= pool.max_seq_len and pool.prepare_write(slot, new_len):
                    if pool.stats["cow_copies"]:
                        saw["cow"] = True
                    pool.advance(slot, g)
                    # deterministic continuation: same prefix -> same tokens,
                    # so decoded pages are shareable too
                    ext = (ctx.sum() + np.arange(g)) % 50
                    slots[slot] = ctx = np.concatenate([ctx, ext.astype(np.int32)])
                    pool.register_prefix(slot, ctx)
            elif op == 2:  # speculative rollback
                slot = list(slots)[rs.randint(len(slots))]
                live = int(pool.seq_lens[slot])
                if live > 1:
                    n = int(rs.randint(1, min(live, 6)))
                    pool.rollback(slot, n)
                    slots[slot] = slots[slot][: live - n]
            elif op == 3:  # preempt / finish
                slot = list(slots)[rs.randint(len(slots))]
                pool.free_slot(slot)
                del slots[slot]
            elif op == 4:
                pool.defrag()
            else:  # growth that may evict cold cached pages
                slot = list(slots)[rs.randint(len(slots))]
                target = int(pool.seq_lens[slot]) + int(rs.randint(1, 10))
                evicted_before = pool.stats["cache_evictions"]
                if target <= pool.max_seq_len and pool.prepare_write(slot, target):
                    pool.advance(slot, target - int(pool.seq_lens[slot]))
                    ext = np.zeros(target - slots[slot].size, np.int32)
                    if ext.size:
                        slots[slot] = np.concatenate([slots[slot], ext])
                if pool.stats["cache_evictions"] > evicted_before:
                    saw["evict"] = True
            _check_partition(pool)
        # the soak must actually exercise the sharing machinery
        assert saw["attach"], f"seed {seed}: no prefix attach happened"
        assert pool.stats["registered_pages"] > 0
