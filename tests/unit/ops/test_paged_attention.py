"""Paged attention front-end tests (``ops/transformer/paged_attention.py``).

The serving layer depends on three invariants: the XLA scatter + gather form
and the Pallas page-table kernel agree, sentinel/garbage table entries past the
live length never leak into outputs, and GQA is computed by grouping —
never by materializing an NH-wide cache copy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.paged_attention import (
    paged_prefill_attention,
    ragged_paged_attention,
)


def _rand_pool(rs, NP, NKV, P, D):
    k = rs.randn(NP, NKV, P, D).astype(np.float32)
    v = rs.randn(NP, NKV, P, D).astype(np.float32)
    return jnp.asarray(k), jnp.asarray(v)


def _stack(pool):
    """A per-layer pool [NP, NKV, P, D] as layer 1 of the two-layer stack the
    entries take, under a layer of garbage that a wrong index would read."""
    return jnp.stack([jnp.full(pool.shape, 1e6, pool.dtype), pool])


def _window_rows(pool, page_table, kv_lens, q_lens, W):
    """[R, W, NKV, D]: what the pool holds at each row's window positions
    ``kv_len - q_len ..`` (zeros in the slots past ``q_len``)."""
    pool, pt = np.asarray(pool), np.asarray(page_table)
    _, NKV, P, D = pool.shape
    rows = np.zeros((len(pt), W, NKV, D), np.float32)
    for r, (kv, ql) in enumerate(zip(np.asarray(kv_lens), np.asarray(q_lens))):
        for w in range(ql):
            pos = kv - ql + w
            rows[r, w] = pool[pt[r, pos // P], :, pos % P]
    return jnp.asarray(rows)


def _ragged(q, kp, vp, pt, kv_lens, q_lens, impl):
    """``ragged_paged_attention`` on a pool that already holds the windows'
    keys and values: the entry writes them again, which must change no page
    but the trash page, and attends."""
    W = q.shape[1]
    out, k2, v2 = ragged_paged_attention(
        q, _window_rows(kp, pt, kv_lens, q_lens, W), _window_rows(vp, pt, kv_lens, q_lens, W),
        _stack(kp), _stack(vp), 1, pt, kv_lens, q_lens, impl=impl,
    )
    for new, old in ((k2, kp), (v2, vp)):
        np.testing.assert_array_equal(np.asarray(new[1, 1:]), np.asarray(old[1:]))
        assert (np.asarray(new[0]) == 1e6).all()
    return np.asarray(out)


def _decode(q, kp, vp, pt, lens, impl):
    """Decode rows of the ragged entry: one token ``q`` [B, NH, D] a row,
    the newest of the row's ``lens`` keys (``q_lens == 1``; a row of length
    0 is dead)."""
    lens = jnp.asarray(lens, jnp.int32)
    return _ragged(jnp.asarray(q)[:, None], kp, vp, jnp.asarray(pt), lens, (lens > 0).astype(jnp.int32), impl)[:, 0]


def _dense_from_pages(k_pages, page_table, P):
    """[B, S, NKV, D] linear cache equivalent of a page table (numpy ref)."""
    kp = np.asarray(k_pages)
    pt = np.asarray(page_table)
    B, maxp = pt.shape
    _, NKV, _, D = kp.shape
    out = np.zeros((B, maxp * P, NKV, D), np.float32)
    for b in range(B):
        for i, pid in enumerate(pt[b]):
            if pid >= 0:
                out[b, i * P : (i + 1) * P] = kp[pid].transpose(1, 0, 2)
    return out


def _ref_decode(q, k_lin, v_lin, lens, scale):
    B, NH, D = q.shape
    NKV = k_lin.shape[2]
    G = NH // NKV
    out = np.zeros((B, NH, D), np.float32)
    for b in range(B):
        L = int(lens[b])
        if L == 0:
            continue
        for h in range(NH):
            kv = h // G
            s = (k_lin[b, :L, kv] @ q[b, h]) * scale
            p = np.exp(s - s.max())
            p /= p.sum()
            out[b, h] = p @ v_lin[b, :L, kv]
    return out


@pytest.mark.parametrize("nkv", [4, 2, 1])  # MHA, GQA, MQA
def test_xla_fallback_matches_reference(nkv):
    B, NH, D, P, NP, maxp = 3, 4, 16, 8, 12, 4
    rs = np.random.RandomState(0)
    q = rs.randn(B, NH, D).astype(np.float32)
    kp, vp = _rand_pool(rs, NP, nkv, P, D)
    # ragged tables: unused tail entries are -1 sentinels
    pt = np.full((B, maxp), -1, np.int32)
    pt[0, :3] = [3, 7, 1]
    pt[1, :1] = [5]
    pt[2, :4] = [2, 9, 4, 8]
    lens = np.array([20, 8, 32], np.int32)
    out = _decode(q, kp, vp, pt, lens, "xla")
    ref = _ref_decode(
        q, _dense_from_pages(kp, pt, P), _dense_from_pages(vp, pt, P),
        lens, 1.0 / np.sqrt(D),
    )
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_xla_matches_pallas_interpret():
    B, NH, nkv, D, P, NP, maxp = 2, 4, 2, 16, 8, 10, 3
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.randn(B, NH, D).astype(np.float32))
    kp, vp = _rand_pool(rs, NP, nkv, P, D)
    pt = np.full((B, maxp), -1, np.int32)
    pt[0, :2] = [4, 2]
    pt[1, :3] = [7, 1, 9]
    lens = np.array([13, 24], np.int32)
    out_x = _decode(q, kp, vp, pt, lens, "xla")
    out_p = _decode(q, kp, vp, pt, lens, "pallas")
    np.testing.assert_allclose(out_x, out_p, rtol=2e-5, atol=2e-5)


def test_zero_length_rows_and_garbage_pages_are_inert():
    B, NH, nkv, D, P, NP, maxp = 2, 2, 2, 8, 4, 6, 2
    rs = np.random.RandomState(2)
    q = jnp.asarray(rs.randn(B, NH, D).astype(np.float32))
    kp, vp = _rand_pool(rs, NP, nkv, P, D)
    pt = np.array([[3, -1], [-1, -1]], np.int32)
    lens = np.array([4, 0], np.int32)
    out = _decode(q, kp, vp, pt, lens, "xla")
    assert (out[1] == 0).all()  # dead row: exact zeros (kernel contract)
    # garbage in pages past the live length must not move the output
    kp2 = kp.at[5].set(1e6)
    vp2 = vp.at[5].set(-1e6)
    out2 = _decode(q, kp2, vp2, pt, lens, "xla")
    np.testing.assert_allclose(out, out2, rtol=1e-6)


def test_prefill_chunk_matches_causal_reference():
    B, T, NH, nkv, D, P, NP, maxp = 1, 6, 4, 2, 8, 4, 8, 4
    rs = np.random.RandomState(3)
    q = rs.randn(B, T, NH, D).astype(np.float32)
    kp, vp = _rand_pool(rs, NP, nkv, P, D)
    pt = np.array([[2, 5, 1, -1]], np.int32)
    start = 3  # chunk positions 3..8: prefix 0..2 already in the pages
    q_pos = np.arange(start, start + T, dtype=np.int32)[None]
    out = paged_prefill_attention(
        jnp.asarray(q), _stack(kp), _stack(vp), 1, jnp.asarray(pt), jnp.asarray(q_pos), jnp.asarray([start + T])
    )
    k_lin = _dense_from_pages(kp, pt, P)
    v_lin = _dense_from_pages(vp, pt, P)
    scale = 1.0 / np.sqrt(D)
    for t in range(T):
        ref = _ref_decode(
            q[:, t], k_lin, v_lin, np.array([start + t + 1]), scale
        )
        np.testing.assert_allclose(
            np.asarray(out[:, t]), ref, rtol=2e-5, atol=2e-5,
            err_msg=f"chunk offset {t}",
        )


# --- ragged mixed-row attention (ISSUE 8) -----------------------------------
def _ragged_fixture(rs, R=3, W=6, NH=4, nkv=2, D=16, P=8, NP=12, maxp=4):
    """A genuinely mixed window: row 0 decodes (q_len 1), row 1 runs a
    prefill chunk filling its window (q_len W), row 2 is dead padding."""
    q = jnp.asarray(rs.randn(R, W, NH, D).astype(np.float32))
    kp, vp = _rand_pool(rs, NP, nkv, P, D)
    pt = np.full((R, maxp), -1, np.int32)
    pt[0, :3] = [3, 7, 1]
    pt[1, :1] = [5]
    kv_lens = np.array([18, W, 0], np.int32)  # INCLUDING this step's tokens
    q_lens = np.array([1, W, 0], np.int32)
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(kv_lens), jnp.asarray(q_lens)


def test_ragged_matches_per_mode_reference():
    """Each row of a mixed window must equal its single-mode computation:
    the decode row matches masked decode attention at its length, the
    chunk row matches the causal per-position reference, and the dead row
    is exact zeros."""
    rs = np.random.RandomState(4)
    q, kp, vp, pt, kv_lens, q_lens = _ragged_fixture(rs)
    W, D, P = q.shape[1], q.shape[3], kp.shape[2]
    out = _ragged(q, kp, vp, pt, kv_lens, q_lens, "xla")
    k_lin = _dense_from_pages(kp, pt, P)
    v_lin = _dense_from_pages(vp, pt, P)
    scale = 1.0 / np.sqrt(D)
    # decode row: one token at position kv_len-1 sees the whole prefix
    ref0 = _ref_decode(np.asarray(q[0:1, 0]), k_lin[0:1], v_lin[0:1],
                       np.array([18]), scale)
    np.testing.assert_allclose(out[0:1, 0], ref0, rtol=2e-5, atol=2e-5)
    # chunk row: causal per position (start 0: kv_len == q_len)
    for t in range(W):
        ref1 = _ref_decode(np.asarray(q[1:2, t]), k_lin[1:2], v_lin[1:2],
                           np.array([t + 1]), scale)
        np.testing.assert_allclose(out[1:2, t], ref1, rtol=2e-5, atol=2e-5,
                                   err_msg=f"chunk offset {t}")
    assert (out[2] == 0).all()  # dead row: exact zeros


def test_ragged_xla_matches_pallas_interpret():
    """The Pallas ragged kernel (scalar-prefetched page table + per-row
    (kv_len, q_len) metadata) agrees with the XLA gather fallback on every
    LIVE window slot; dead rows are zeros in both."""
    rs = np.random.RandomState(5)
    q, kp, vp, pt, kv_lens, q_lens = _ragged_fixture(rs)
    out_x = _ragged(q, kp, vp, pt, kv_lens, q_lens, "xla")
    out_p = _ragged(q, kp, vp, pt, kv_lens, q_lens, "pallas")
    for r, ql in enumerate(np.asarray(q_lens)):
        np.testing.assert_allclose(
            out_x[r, :ql], out_p[r, :ql], rtol=2e-5, atol=2e-5, err_msg=f"row {r}"
        )
    assert (out_p[2] == 0).all()


def test_ragged_mid_sequence_verify_row():
    """A verify-shaped row (q_len 3 starting mid-sequence) must score each
    slot causally against prefix + earlier slots — the accepted-prefix
    computation depends on it."""
    rs = np.random.RandomState(6)
    R, W, NH, nkv, D, P, NP, maxp = 1, 4, 4, 2, 8, 4, 8, 4
    q = jnp.asarray(rs.randn(R, W, NH, D).astype(np.float32))
    kp, vp = _rand_pool(rs, NP, nkv, P, D)
    pt = np.array([[2, 5, 1, -1]], np.int32)
    start, ql = 5, 3  # tokens at positions 5, 6, 7; slot 3 is pad garbage
    kv_lens = np.array([start + ql], np.int32)
    q_lens = np.array([ql], np.int32)
    out = _ragged(q, kp, vp, jnp.asarray(pt), jnp.asarray(kv_lens), jnp.asarray(q_lens), "xla")
    k_lin = _dense_from_pages(kp, pt, P)
    v_lin = _dense_from_pages(vp, pt, P)
    for t in range(ql):
        ref = _ref_decode(np.asarray(q[:, t]), k_lin, v_lin,
                          np.array([start + t + 1]), 1.0 / np.sqrt(D))
        np.testing.assert_allclose(out[:, t], ref, rtol=2e-5, atol=2e-5,
                                   err_msg=f"verify slot {t}")
    # garbage k/v in the tabled page past the live length (table slot 2 =
    # positions 8..11, all >= kv_len 8) never leak in
    kp2 = kp.at[1].set(1e6)
    vp2 = vp.at[1].set(-1e6)
    out2 = _ragged(q, kp2, vp2, jnp.asarray(pt), jnp.asarray(kv_lens), jnp.asarray(q_lens), "xla")
    np.testing.assert_allclose(out[:, :ql], out2[:, :ql], rtol=1e-6)


# --- the fused write-and-attend kernel against XLA's scatter + gather --------
_P, _MAXP = 8, 8  # pages of 8 positions, tables of 8 slots


def _fused_rows(W):
    """Rows as ``(keys before the step, new tokens, pages the table's tail is
    padded with)``, for a window of ``W`` slots."""
    full = _P * _MAXP
    return {
        "decode_row": (10, 1, -1),
        "dead_row": (0, 0, -1),
        "parked_row": (12, 0, -1),  # no new token on live keys: nothing read, nothing written
        "ends_on_a_page_boundary": (2 * _P - min(W, 3), min(W, 3), -1),
        "writes_across_a_page_boundary": (_P - 1, min(W, 4), -1),
        "first_token_of_a_fresh_page": (_P, 1, -1),
        "fills_the_last_table_slot": (full - min(W, 5), min(W, 5), -1),
        "window_from_an_empty_row": (0, W, -1),
        "more_pages_than_a_buffer": (5 * _P + 3, min(W, 2), -1),
        "trash_page_in_the_dead_tail": (10, 1, 0),
    }


_GQA_4_2, _GQA_8_2, _MHA_4_4, _TP_2_1 = (4, 2), (8, 2), (4, 4), (2, 1)  # (query heads, kv heads)
_LAYOUTS = (_GQA_4_2, _GQA_8_2, _MHA_4_4, _TP_2_1)
_WIDTHS = (1, 4, 16)  # decode, verify (K = 3 drafts), a chunk of two pages


@pytest.mark.parametrize(
    "case, heads, D, W, pages_per_buffer, layer",
    # every row kind in one window: each head layout and width on the kernel that walks live pages
    # (a head of whole lanes), a half-buffer of two pages and, a layout a width, one that holds a whole table ...
    [("all_rows", heads, 128, W, 2, 1) for heads in _LAYOUTS for W in _WIDTHS]
    + [("all_rows", heads, 128, W, None, 1) for heads, W in zip(_LAYOUTS, _WIDTHS)]
    # ... and on the grid kernel that narrow heads keep
    + [("all_rows", heads, 64, W, None, 1) for heads, W in zip(_LAYOUTS, _WIDTHS + (4,))]
    + [("all_rows", _GQA_8_2, 64, W, None, 1) for W in (1, 16)]
    + [("all_rows", _GQA_8_2, D, 4, ppb, layer) for D, ppb in ((128, 2), (64, None)) for layer in (0, 2)]
    # a row kind alone
    + [(case, _GQA_8_2, 128, 4, 2, 1) for case in sorted(_fused_rows(4))]
    + [(case, _GQA_8_2, 64, 4, None, 1) for case in ("decode_row", "writes_across_a_page_boundary", "dead_row")],
)
def test_fused_ragged_kernel_matches_xla_scatter_then_gather(case, heads, D, W, pages_per_buffer, layer):
    """The Pallas kernel (interpret mode) merges the window's keys and values
    into the pages that receive them as it reads them: every page but the
    trash page must hold the bytes XLA's scatter leaves there, in the written
    layer and in the two others, and no page that receives nothing may change
    (the kernel that walks live pages never touches the trash page; the grid
    kernel leaves it finite); the output must be bit for bit what the same
    kernel gives on the scattered pool, and the XLA gather's within float32
    rounding; a row with no new token is exact zeros."""
    from deepspeed_tpu.ops.transformer.decode_attention import ragged_paged_attention as kernel

    (NH, NKV), L = heads, 3
    kinds = _fused_rows(W)
    kinds = kinds if case == "all_rows" else {case: kinds[case]}
    rows, next_page = [], 1
    for before, n, pad in kinds.values():
        held = -(-(before + n) // _P)
        rows.append((before, n, list(range(next_page, next_page + held)) + [pad] * (_MAXP - held)))
        next_page += held
    NP = next_page
    rs = np.random.RandomState(7)
    k0 = jnp.asarray(rs.randn(L, NP, NKV, _P, D).astype(np.float32))
    v0 = jnp.asarray(rs.randn(L, NP, NKV, _P, D).astype(np.float32))
    q = jnp.asarray(rs.randn(len(rows), W, NH, D).astype(np.float32))
    k_new = jnp.asarray(rs.randn(len(rows), W, NKV, D).astype(np.float32))
    v_new = jnp.asarray(rs.randn(len(rows), W, NKV, D).astype(np.float32))
    q_lens = jnp.asarray([n for _, n, _ in rows], jnp.int32)
    kv_lens = jnp.asarray([before + n for before, n, _ in rows], jnp.int32)
    pt = jnp.asarray([table for _, _, table in rows], jnp.int32)

    @jax.jit  # one program each: op by op, either form compiles its dozens of ops alone, seconds a case
    def fused(k_pages, v_pages):
        return kernel(
            q, k_new, v_new, k_pages, v_pages, layer, pt, kv_lens, q_lens,
            interpret=True, pages_per_buffer=pages_per_buffer,
        )

    @jax.jit
    def scatter_then_gather(k_pages, v_pages):
        return ragged_paged_attention(q, k_new, v_new, k_pages, v_pages, layer, pt, kv_lens, q_lens, impl="xla")

    out_x, k_x, v_x = scatter_then_gather(k0, v0)
    out_p, k_p, v_p = fused(k0, v0)
    for got, scattered, before in ((k_p, k_x, k0), (v_p, v_x, v0)):
        np.testing.assert_array_equal(np.asarray(got[:, 1:]), np.asarray(scattered[:, 1:]))
        others = [l for l in range(L) if l != layer]
        np.testing.assert_array_equal(np.asarray(got)[others], np.asarray(before)[others])
        if D % 128 == 0:
            np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(before[:, 0]))
        assert np.isfinite(np.asarray(got[:, 0])).all()  # the trash page
        written = (np.asarray(got[layer, 1:]) != np.asarray(before[layer, 1:])).any(axis=(1, 2, 3))
        receiving = {
            table[pos // _P] for before_len, n, table in rows for pos in range(before_len, before_len + n)
        }
        assert set(1 + np.flatnonzero(written)) == receiving
    out_again, _, _ = fused(k_x, v_x)  # XLA's scatter, then the kernel
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_again))
    assert np.isfinite(np.asarray(out_p)).all()
    for r, (_, n, _) in enumerate(rows):
        np.testing.assert_allclose(
            np.asarray(out_p)[r, :n], np.asarray(out_x)[r, :n], rtol=2e-5, atol=2e-5,
            err_msg=f"row {r}",
        )
        if n == 0 and (D % 128 == 0 or int(kv_lens[r]) == 0):  # the grid kernel: a row without keys
            assert (np.asarray(out_p)[r] == 0).all()


# --- heads narrower than a lane tile, ``f`` to a page ------------------------
def _shared(pool, f):
    """An unpacked pool ``[L, NP, NKV, P, D]`` as the pool that holds ``f`` heads
    a group, ``[L, NP, NKV / f, P, f D]``: head ``j`` in group ``j // f`` at lanes
    ``(j % f) D ..``."""
    L, NP, NKV, P, D = pool.shape
    return pool.reshape(L, NP, NKV // f, f, P, D).transpose(0, 1, 2, 4, 3, 5).reshape(L, NP, NKV // f, P, f * D)


def _unshared(pool, f):
    L, NP, G, P, lanes = pool.shape
    return pool.reshape(L, NP, G, P, f, lanes // f).transpose(0, 1, 2, 4, 3, 5).reshape(L, NP, G * f, P, lanes // f)


def _narrow_head_window(D, NKV, Hg, W, seed=11):
    """A window of every row kind at heads of ``D``: a decode row, a dead row, a
    row that writes across a page boundary and one whose window starts an
    empty row (at ``W = 16`` a chunk that spans two pages). Returns the
    entry's arguments with UNPACKED pools."""
    rs = np.random.RandomState(seed)
    rows = [(10, 1), (0, 0), (_P - 1, min(W, 4)), (0, W)]
    maxp, L, R = 3, 2, 4
    arr = lambda *shape: jnp.asarray(rs.randn(*shape).astype(np.float32))
    pools = arr(L, 1 + R * maxp, NKV, _P, D), arr(L, 1 + R * maxp, NKV, _P, D)
    window = arr(R, W, NKV * Hg, D), arr(R, W, NKV, D), arr(R, W, NKV, D)
    table = jnp.asarray(1 + rs.permutation(R * maxp).reshape(R, maxp), jnp.int32)
    q_lens = jnp.asarray([n for _, n in rows], jnp.int32)
    kv_lens = jnp.asarray([before + n for before, n in rows], jnp.int32)
    return window, pools, (1, table, kv_lens, q_lens)


@functools.lru_cache(maxsize=None)
def _entry(impl, window=None, with_sinks=False):
    """The entry as one compiled program (called op by op it compiles each of
    its dozens of ops alone, seconds a case)."""

    def call(q, k_new, v_new, k_pages, v_pages, layer, table, kv_lens, q_lens, sinks):
        extras = dict(window=window, sinks=sinks) if window or with_sinks else {}
        return ragged_paged_attention(q, k_new, v_new, k_pages, v_pages, layer, table, kv_lens, q_lens, impl=impl, **extras)

    return jax.jit(call)


@functools.lru_cache(maxsize=None)
def _unpacked_reference(D, NKV, Hg, W, window=None, with_sinks=False):
    """(the window, the pools before, the rest of the entry's arguments, what XLA's form gives on the unpacked pools)."""
    operands, pools, rest = _narrow_head_window(D, NKV, Hg, W)
    rest += (jnp.linspace(-1.0, 1.0, NKV * Hg) if with_sinks else None,)
    return operands, pools, rest, _entry("xla", window, with_sinks)(*operands, *pools, *rest)


def _assert_shared_lanes_agree(D, NKV, Hg, W, impl, **extras):
    """The packed pool through ``impl`` against the unpacked pool through XLA:
    live slots' outputs, a dead row's zeros, and, unpacked, every page but the
    trash page byte for byte."""
    f = 128 // D
    operands, pools, rest, (want, *want_pools) = _unpacked_reference(D, NKV, Hg, W, **extras)
    got, *got_pools = _entry(impl, **extras)(*operands, *(_shared(p, f) for p in pools), *rest)
    assert got.shape == want.shape
    for a, b, before in zip(got_pools, want_pools, pools):
        assert a.shape == _shared(before, f).shape
        np.testing.assert_array_equal(np.asarray(_unshared(a, f))[:, 1:], np.asarray(b)[:, 1:])
        assert (np.asarray(b)[1, 1:] != np.asarray(before)[1, 1:]).any()  # something was written
    for r, n in enumerate(np.asarray(rest[3])):
        np.testing.assert_allclose(np.asarray(got)[r, :n], np.asarray(want)[r, :n], rtol=2e-5, atol=2e-5, err_msg=f"row {r}")
    assert not np.asarray(got)[1].any()  # the dead row


_GRANITE, _MHA_64, _FOUR_A_TILE = (64, 8, 4), (64, 2, 1), (32, 4, 2)  # (head_dim, kv heads, query heads a kv head)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize(
    "layout, W",
    # decode, verify (K = 3 drafts), a chunk of two pages at granite's heads; the two others a width each
    [(_GRANITE, W) for W in _WIDTHS] + [(_MHA_64, 16), (_FOUR_A_TILE, 1), (_FOUR_A_TILE, 4)],
)
def test_heads_that_share_a_lane_tile_are_the_unpacked_attention(monkeypatch, layout, W, impl):
    """``f = 128 // D`` KV heads side by side on a page's lanes (the pool of
    ``kv_pool.heads_per_group``): the entry widens q, k and v to heads of 128,
    BOTH implementations attend as if the group were one KV head, and what
    comes back is the unpacked pool's attention, with the unpacked pool's
    bytes in every page. The Pallas form is the kernel that walks live pages
    (interpreted): the grid kernel, which pages of 64 lanes took, is out of reach."""
    from deepspeed_tpu.ops.transformer import decode_attention

    monkeypatch.setattr(decode_attention, "_ragged_by_grid", None)  # reaching it would raise
    _assert_shared_lanes_agree(*layout, W, impl)


@pytest.mark.parametrize("extras", [{"window": 5}, {"with_sinks": True}], ids=["window", "sinks"])
def test_shared_lanes_bring_windows_and_sinks_to_heads_of_64(extras):
    """A window and sinks at heads of 64, which the grid kernel refuses: query
    heads keep their order through the widening, so ``sinks [NH]`` is passed
    as it is."""
    _assert_shared_lanes_agree(*_GRANITE, 4, "pallas", **extras)


@pytest.mark.parametrize("D, NKV", [(64, 3), (24, 4)], ids=["three_heads_of_64", "heads_of_24"])
def test_heads_that_do_not_pack_keep_the_unpacked_pool(D, NKV):
    """An odd head count a shard, a width that does not divide 128: one head a
    group, today's pool, and the two implementations still agree on it."""
    from deepspeed_tpu.inference.kv_pool import heads_per_group, page_shapes

    assert heads_per_group(D, D, NKV) == 1
    assert page_shapes(2, 13, NKV, _P, D, D, 1) == ((2, 13, NKV, _P, D),) * 2
    operands, pools, rest, (want, *want_pools) = _unpacked_reference(D, NKV, 2, 4)
    got, *got_pools = _entry("pallas")(*operands, *pools, *rest)
    for a, b in zip(got_pools, want_pools):
        np.testing.assert_array_equal(np.asarray(a)[:, 1:], np.asarray(b)[:, 1:])
    for r, n in enumerate(np.asarray(rest[3])):
        np.testing.assert_allclose(np.asarray(got)[r, :n], np.asarray(want)[r, :n], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("W", [1, 128])
def test_ragged_kernel_jaxpr_does_not_grow_with_pages_heads_or_tables(W):
    """What a process pays at set-up for every serving program is the Python
    tracing and the lowering of this kernel's body, which no compilation cache
    holds (PR 26 was refused for two seconds of it). The body walks buffers,
    pages, kv heads and tiles in rolled loops, so its jaxpr is as long for 2
    pages a half-buffer as for 8, for 2 kv heads as for 8, for a table of 8
    slots as for 64, and short: an unrolled loop shows here, not in the
    driver's ``setup_s``."""
    from deepspeed_tpu.analysis import iter_eqns
    from deepspeed_tpu.ops.transformer.decode_attention import ragged_paged_attention as kernel

    def equations(NKV=2, maxp=8, pages_per_buffer=2):
        R, Hg, D, P, L = 4, 4, 128, 64, 2
        f32, i32 = jnp.float32, jnp.int32
        shapes = [
            jax.ShapeDtypeStruct((R, W, NKV * Hg, D), f32), *[jax.ShapeDtypeStruct((R, W, NKV, D), f32)] * 2,
            *[jax.ShapeDtypeStruct((L, R * maxp + 1, NKV, P, D), f32)] * 2,
            jax.ShapeDtypeStruct((R, maxp), i32), *[jax.ShapeDtypeStruct((R,), i32)] * 2,
        ]
        jaxpr = jax.make_jaxpr(
            lambda q, kn, vn, kp, vp, pt, kl, ql: kernel(
                q, kn, vn, kp, vp, 1, pt, kl, ql, interpret=False, pages_per_buffer=pages_per_buffer
            )
        )(*shapes)
        return sum(1 for _ in iter_eqns(jaxpr))  # the kernel's body, its loops' and branches' included

    base = equations()
    assert base == equations(pages_per_buffer=8) == equations(NKV=8) == equations(maxp=64, pages_per_buffer=None)
    assert base < 350, base  # 264 at width 1 and 274 at width 128 when written (PR 27)


def test_gqa_grouped_equals_repeat_expansion():
    """The grouped-einsum GQA math must equal the (banned) NH-wide repeat."""
    B, NH, nkv, D, P, NP, maxp = 2, 8, 2, 16, 8, 8, 2
    rs = np.random.RandomState(4)
    q = rs.randn(B, NH, D).astype(np.float32)
    kp, vp = _rand_pool(rs, NP, nkv, P, D)
    pt = np.array([[1, 4], [6, -1]], np.int32)
    lens = np.array([12, 5], np.int32)
    out = _decode(q, kp, vp, pt, lens, "xla")
    # reference: expand kv to NH heads, per-head attention
    k_lin = _dense_from_pages(kp, pt, P).repeat(NH // nkv, axis=2)
    v_lin = _dense_from_pages(vp, pt, P).repeat(NH // nkv, axis=2)
    ref = np.zeros((B, NH, D), np.float32)
    scale = 1.0 / np.sqrt(D)
    for b in range(B):
        for h in range(NH):
            s = (k_lin[b, : lens[b], h] @ q[b, h]) * scale
            p = np.exp(s - s.max())
            p /= p.sum()
            ref[b, h] = p @ v_lin[b, : lens[b], h]
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_dense_fallback_gqa_has_no_repeat():
    """decode.py's dense GQA fallback: grouped einsum matches the repeat
    reference, and the lowered HLO contains no NH-wide cache broadcast
    (satellite guard for the jnp.repeat blowup fix)."""
    from deepspeed_tpu.inference.decode import _cached_attention
    from deepspeed_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, hidden_size=64, num_layers=1, num_heads=8,
        num_kv_heads=2, max_seq_len=32, flash_attention=False, dtype="float32",
    )
    B, T, S = 2, 3, 17  # S deliberately not a multiple of 256 (dense path)
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(B, T, 8, 8).astype(np.float32))
    k = jnp.asarray(rs.randn(B, S, 2, 8).astype(np.float32))
    v = jnp.asarray(rs.randn(B, S, 2, 8).astype(np.float32))
    q_pos = jnp.asarray(np.tile(np.arange(5, 5 + T, dtype=np.int32), (B, 1)))
    mask = jnp.asarray(np.arange(S) < 8)
    out = _cached_attention(cfg, q, k, v, q_pos, mask)
    # repeat-based reference
    kr = jnp.repeat(k, 4, axis=2)
    vr = jnp.repeat(v, 4, axis=2)
    scores = jnp.einsum("btnd,bsnd->bnts", q, kr).astype(jnp.float32) / np.sqrt(8)
    causal = q_pos[:, None, :, None] >= jnp.arange(S)[None, None, None, :]
    scores = jnp.where(causal & mask[None, None, None, :], scores, -1e30)
    ref = jnp.einsum("bnts,bsnd->btnd", jax.nn.softmax(scores, -1), vr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    # structural guard: no intermediate may materialize an NH-wide cache
    # copy [B, S, NH, D] (what jnp.repeat(k_cache, G, axis=2) produced) —
    # checked by the analysis layer's recursive shape scan (sees through
    # scan/pjit bodies, unlike the old top-level eqn loop)
    from deepspeed_tpu.analysis import find_aval_shapes

    jaxpr = jax.make_jaxpr(
        lambda q, k, v: _cached_attention(cfg, q, k, v, q_pos, mask)
    )(q, k, v)
    banned = (B, S, 8, 8)
    hits = find_aval_shapes(jaxpr, banned)
    assert not hits, f"decode fallback materializes an NH-wide cache: {hits}"
    # legacy cross-check (top-level eqns only): keeps the analysis helper
    # honest against a hand-rolled scan of the same jaxpr
    for eqn in jaxpr.jaxpr.eqns:
        for var in eqn.outvars:
            assert tuple(getattr(var.aval, "shape", ())) != banned, (
                f"decode fallback materializes an NH-wide cache: {eqn.primitive}"
            )


def test_training_gqa_attention_has_no_repeat():
    """Satellite guard for the training-side GQA fix: the grouped einsum
    path in ``_local_full_attention`` must not materialize NH-wide k/v
    copies (what ``_expand_gqa``'s jnp.repeat produced). With grouping,
    the ONLY [B, T, NH, D] tensor in the attention body is the final
    output reshape; an expansion-based path adds NH-wide k and v too."""
    from deepspeed_tpu.analysis import find_aval_shapes
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(
        vocab_size=64, hidden_size=64, num_layers=1, num_heads=8,
        num_kv_heads=2, max_seq_len=16, flash_attention=False, dtype="float32",
    )
    model = TransformerLM(cfg)
    B, T, NH, NKV, D = 2, 16, 8, 2, 8
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(B, T, NH, D).astype(np.float32))
    k = jnp.asarray(rs.randn(B, T, NKV, D).astype(np.float32))
    v = jnp.asarray(rs.randn(B, T, NKV, D).astype(np.float32))
    pos = jnp.asarray(np.tile(np.arange(T, dtype=np.int32), (B, 1)))
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: model._local_full_attention(q, k, v, pos, 1.0 / np.sqrt(D))
    )(q, k, v)
    nh_wide = find_aval_shapes(jaxpr, (B, T, NH, D))
    assert len(nh_wide) <= 1, (
        f"NH-wide tensors materialized in GQA attention (expansion?): {nh_wide}"
    )
    grouped = find_aval_shapes(jaxpr, (B, T, NKV, NH // NKV, D))
    assert grouped, "grouped [B,T,NKV,G,D] factoring missing — GQA regressed"
    # numerics: grouped math equals the repeat-expansion reference
    out = model._local_full_attention(q, k, v, pos, 1.0 / np.sqrt(D))
    kr, vr = jnp.repeat(k, NH // NKV, axis=2), jnp.repeat(v, NH // NKV, axis=2)
    ref = model._local_full_attention(q, kr, vr, pos, 1.0 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
