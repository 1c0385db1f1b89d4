"""The ragged paged kernel at groups that are no power of two: 6 and 9 query
heads a KV head (Laguna-S-2.1's 48 and 72 over 8), with and without a window.
The kernel maps a query row to its window slot by ``row // Hg`` (queries lie
W-major, slot ``w`` of group head ``h`` at row ``w * Hg + h``) and cuts a wide
window's rows into query tiles of at most 128: at these groups a tile's edge
falls INSIDE a group (64 x 6 rows in tiles of 128, 64 x 9 in tiles of 96), which
no accepted model's group (1, 4, 8, 16) ever does.

The Pallas kernel itself, interpreted, against a plain masked softmax in NumPy
over each row's whole history, rows stepped as the server steps them: chunks on
the chunk grid, then single tokens, ragged lengths, rows that end early (dead
rows beside live ones), more than a lap round a window layer's ring. After every
call the pools are compared with what they were: the pages the call's rows
were to write hold the new keys and values, the trash page may hold anything
finite, and NO other page has changed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import decode_attention
from tests.unit.ops.test_windowed_paged_attention import _plain

P, D, NKV, CHUNK = 8, 128, 2, 64


@functools.lru_cache(maxsize=None)
def _kernel(window):
    return jax.jit(functools.partial(decode_attention.ragged_paged_attention, interpret=True, window=window, scale=D ** -0.5))


def _serve(group, window, lens, prefill, seed=0, P=P, NKV=NKV, CHUNK=CHUNK):
    """Every sequence of ``lens`` through the kernel: chunks of ``CHUNK`` from
    position 0 up to ``prefill[r]`` tokens, then one token a call; the pools
    checked after every call. Returns the served and the plain outputs."""
    rs = np.random.RandomState(seed)
    R, T, NH = len(lens), max(lens), group * NKV
    maxp = -(-T // P)
    ring = (-(-CHUNK // P) + -(-(window - 1) // P)) if window else maxp
    q = rs.randn(R, T, NH, D).astype(np.float32)
    k = rs.randn(R, T, NKV, D).astype(np.float32)
    v = rs.randn(R, T, NKV, D).astype(np.float32)
    # two layers' pools, the kernel on layer 1: layer 0's pages and the other rows' must come back as they went in
    kp = jnp.asarray(rs.randn(2, 1 + R * ring, NKV, P, D).astype(np.float32))
    vp = jnp.asarray(rs.randn(2, 1 + R * ring, NKV, P, D).astype(np.float32))
    table = np.stack([1 + r * ring + np.arange(maxp) % ring for r in range(R)]).astype(np.int32)
    out = np.zeros((R, T, NH, D), np.float32)
    done = np.zeros(R, np.int64)
    while (done < lens).any():
        q_lens = np.array([0 if d >= n else (min(CHUNK, s - d) if d < s else 1) for d, n, s in zip(done, lens, prefill)])
        W = CHUNK if (q_lens > 1).any() else 1
        win = lambda a: np.stack([np.pad(a[r, done[r] : done[r] + q_lens[r]], ((0, W - q_lens[r]), (0, 0), (0, 0))) for r in range(R)])
        before = np.asarray(kp), np.asarray(vp)
        o, kp, vp = _kernel(window)(
            jnp.asarray(win(q)), jnp.asarray(win(k)), jnp.asarray(win(v)), kp, vp, 1, jnp.asarray(table),
            jnp.asarray(np.where(q_lens > 0, done + q_lens, 0), jnp.int32), jnp.asarray(q_lens, jnp.int32),
        )
        for pool, old, new in ((np.asarray(kp), before[0], k), (np.asarray(vp), before[1], v)):
            want = old.copy()
            for r in range(R):
                for pos in range(done[r], done[r] + q_lens[r]):
                    want[1, table[r, pos // P], :, pos % P] = new[r, pos]
            want[1, 0] = pool[1, 0]  # the trash page: whatever dead slots left there
            assert np.isfinite(pool).all() and np.array_equal(pool, want)
        for r in range(R):
            out[r, done[r] : done[r] + q_lens[r]] = np.asarray(o)[r, : q_lens[r]]
            if q_lens[r] == 0:
                assert not np.asarray(o)[r].any()  # a dead row: exact zeros
        done += q_lens
    return [out[r, :n] for r, n in enumerate(lens)], [_plain(q[r, :n], k[r, :n], v[r, :n], window, None, D ** -0.5) for r, n in enumerate(lens)]


@pytest.mark.parametrize("window", [8, None], ids=["window_8", "full"])
@pytest.mark.parametrize("group", [6, 9])
def test_a_group_of_six_or_nine_is_a_plain_masked_softmax(group, window):
    """Three rows of ragged lengths: one prefilled in two chunks (a full one,
    whose 64 x Hg query rows are cut into tiles inside groups, and a ragged
    one) and then decoded, with a window more than once round its ring of 9 pages (72 positions);
    one that is a few decode tokens long and ends early (a dead row beside live
    ones from then on); one prefilled in one short chunk."""
    W, Hg = CHUNK, group
    _, _, TQ, _ = decode_attention._ragged_tiles(NKV, Hg, W, P, D, 12, 4)
    assert W * Hg > TQ and TQ % Hg, "the case must cut a query tile inside a group"
    served, plain = _serve(group, window, lens=[93, 4, 41], prefill=[88, 1, 37])
    for a, b in zip(served, plain):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)  # float32 throughout; the orders of the sums differ


def test_the_windows_edge_at_the_published_ring():
    """Laguna-S-2.1's window layers as the cell runs them, one KV head of the
    eight: 9 query heads a KV head, a 512-key window, pages of 64, chunks of
    128, so a ring of 10 pages (640 positions). One row prefilled in five
    whole chunks (exactly one lap) and a ragged sixth that wraps onto the
    ring's first page, then decoded; a short row beside it. Every query from
    position 511 on sees exactly ``i - 512 < j <= i``: a kernel that kept 511
    or 513 keys, or a ring a page short, would differ from the plain softmax by
    a key's weight (~2e-3), a hundred times the tolerance."""
    from deepspeed_tpu.inference.kv_pool import window_ring_pages

    assert window_ring_pages(512, 64, 128) == 10 == -(-128 // 64) + -(-511 // 64)  # the ring ``_serve`` lays out
    served, plain = _serve(9, 512, lens=[665, 3], prefill=[660, 1], P=64, NKV=1, CHUNK=128)
    for a, b in zip(served, plain):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    # the comparison can see the edge: one key more or fewer is not within the tolerance
    q, k, v = (np.random.RandomState(1).randn(600, n, D).astype(np.float32) for n in (9, 1, 1))
    for wrong in (511, 513):
        assert np.abs(_plain(q, k, v, wrong, None, D ** -0.5) - _plain(q, k, v, 512, None, D ** -0.5))[512:].max() > 1e-3
