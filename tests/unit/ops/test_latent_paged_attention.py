"""The latent paged kernel (``ops/transformer/latent_attention.py``) and its
XLA form against NumPy: ``NH`` query heads over one shared entry a token whose
leading lanes are also the value.

The rows below are stepped as the server steps them (chunks from position 0,
then single tokens), so that every call reads what earlier calls left in the
pages and a chunk's positions cross page boundaries.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.latent_attention import latent_paged_attention

P = 8


def _plain(q, e, value_lanes, scale):
    """``q`` [T, NH, D] against the sequence's own entries ``e`` [T, D]:
    causal softmax in float64, values the entries' leading lanes."""
    T = q.shape[0]
    if not T:
        return np.zeros((0, q.shape[1], value_lanes))
    s = np.einsum("thd,sd->hts", q, e).astype(np.float64) * scale
    s = np.where(np.arange(T)[None, :] <= np.arange(T)[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hts,sd->thd", p, e[:, :value_lanes])


def _serve(impl, lens, chunk=1, NH=4, D=160, Dv=128, lanes=256, seed=0, steps=None, pages_per_buffer=None, spare=0, left=None):
    """Every sequence of ``lens`` through ``latent_paged_attention``: chunks of
    ``chunk`` from position 0 up to ``steps[r]`` tokens (default: all but the
    last three), then one token a call. With ``left``, a sequence's entries
    but its last ``left`` are in its pages already (a long context without
    its thousand calls), and those tokens are served one a call. Returns the
    served outputs, the plain ones ([tokens served, NH, Dv] a sequence), the
    pool, the table and the entries."""
    rs = np.random.RandomState(seed)
    R, T = len(lens), max(lens)
    maxp = -(-T // P)
    q = rs.randn(R, T, NH, D).astype(np.float32)
    e = rs.randn(R, T, D).astype(np.float32)
    pool = np.zeros((2, 1 + R * maxp + spare, P, lanes), np.float32)
    # a row's pages interleaved with the others', so that page ids are not in walk order
    table = np.stack([1 + r + R * np.arange(maxp) for r in range(R)]).astype(np.int32)
    done = np.zeros(R, np.int64) if left is None else np.maximum(np.array(lens) - left, 0)
    for r in range(R):
        at = np.arange(done[r])
        pool[1, table[r, at // P], at % P, :D] = e[r, : done[r]]
    first, pool = done.copy(), jnp.asarray(pool)
    kw = dict(value_lanes=Dv, scale=D ** -0.5, impl=impl)
    if impl == "pallas":
        kw.update(interpret=True, pages_per_buffer=pages_per_buffer)
    call = jax.jit(functools.partial(latent_paged_attention, **kw))
    out = np.zeros((R, T, NH, Dv), np.float32)
    steps = steps or [n - 3 if left is None else 0 for n in lens]
    while (done < lens).any():
        q_lens = np.array([0 if d >= n else (min(chunk, s - d) if d < s else 1) for d, n, s in zip(done, lens, steps)])
        W = chunk if (q_lens > 1).any() else 1
        win = lambda a: np.stack([np.pad(a[r, done[r] : done[r] + q_lens[r]], ((0, W - q_lens[r]),) + ((0, 0),) * (a.ndim - 2)) for r in range(R)])
        # a finished row's table is all sentinels, as the pool's is once its slot is freed
        live_table = np.where((q_lens > 0)[:, None], table, -1)
        o, pool = call(
            jnp.asarray(win(q)), jnp.asarray(win(e)), pool, 1, jnp.asarray(live_table),
            jnp.asarray(np.where(q_lens > 0, done + q_lens, 0), jnp.int32), jnp.asarray(q_lens, jnp.int32),
        )
        o = np.asarray(o)
        for r in range(R):
            out[r, done[r] : done[r] + q_lens[r]] = o[r, : q_lens[r]]
            if q_lens[r] == 0:
                assert not o[r].any()  # a dead row: exact zeros
        done += q_lens
    plain = [_plain(q[r, :n], e[r, :n], Dv, D ** -0.5) for r, n in enumerate(lens)]
    return [out[r, first[r] : n] for r, n in enumerate(lens)], [a[first[r] :] for r, a in enumerate(plain)], np.asarray(pool), table, e


CASES = {
    "decode_rows": dict(lens=[21, 9, 14], chunk=8, steps=[0, 0, 0]),
    "chunks_then_decode": dict(lens=[37, 18, 3], chunk=16),
    # chunks of 12 from 0: the second starts inside page 1 and ends inside page 2
    "page_boundary_inside_a_chunk": dict(lens=[40, 29], chunk=12),
    "ragged_lengths": dict(lens=[33, 1, 17, 8, 25], chunk=16, steps=[20, 0, 17, 5, 16]),
    # a row of length 0 from the first call on: nothing of it is ever walked
    "zero_length_rows": dict(lens=[19, 0, 11, 0], chunk=8, steps=[16, 0, 8, 0]),
    # 20 heads (GLM-4.7-Flash's): a decode row is the narrow form (20 query rows), a chunk of 8 the wide one (160)
    "heads20_chunks_then_decode": dict(NH=20, lens=[37, 18], chunk=8),
    # the narrow form's edges, one decode call a row: contexts of 1, a page -1 / +0 / +1, and around what a key tile
    # and a half hold when a half is 6 pages (24 and 48 keys) or 2; the new entry on a page's first row (1, 9, 25,
    # 49, 97) and on its last (8, 24, 48)
    "heads20_context_edges": dict(NH=20, lens=[1, 7, 8, 9, 23, 24, 25, 47, 48, 49, 97], left=1),
    # the second call reads what the first wrote, across a page's and a half's end
    "heads20_two_calls": dict(NH=20, lens=[2, 9, 25, 33, 49, 50, 64], left=2),
    "heads20_dead_rows_between_live_ones": dict(NH=20, lens=[33, 0, 0, 18, 0, 41, 0], left=1),
    "heads20_64_ragged_rows": dict(NH=20, lens=[(7 * r * r + 3 * r) % 90 if r % 9 else 0 for r in range(64)], left=1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_xla_form_against_numpy(case):
    served, plain, *_ = _serve("xla", **CASES[case])
    for a, b in zip(served, plain):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)  # float32 throughout; the orders of the sums differ


def _halves(case):
    """Pages a half: as the tile rule has it, one and two, so that a row's
    walk spans several halves; for 20 heads six (the narrow form's key tiles
    then come in two sizes), and all four for the contexts at the edges."""
    if not case.startswith("heads20"):
        return [None, 1, 2]
    return [None, 1, 2, 6] if case == "heads20_context_edges" else [None, 6]


@pytest.mark.parametrize("case,pages_per_buffer", [(case, n) for case in sorted(CASES) for n in _halves(case)])
def test_kernel_against_numpy(case, pages_per_buffer):
    """The Pallas kernel (interpreted) at lane-whole widths: entries of 160
    (128 + 32) in pages of 256 lanes, values the leading 128."""
    served, plain, *_ = _serve("pallas", pages_per_buffer=pages_per_buffer, **CASES[case])
    for a, b in zip(served, plain):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_only_the_written_pages_and_the_trash_page_change(impl):
    """After serving, a row's pages hold its entries (zeros in the pad lanes),
    and the other layer, the spare pages and the pages past a row's length are
    what they were: zeros. The trash page 0 may hold anything finite."""
    lens = [27, 10, 0]
    _, _, pool, table, e = _serve(impl, lens, chunk=12, spare=3)
    assert np.isfinite(pool).all()
    assert not pool[0].any()  # the calls named layer 1
    expected = np.zeros_like(pool[1])
    for r, n in enumerate(lens):
        for t in range(n):
            expected[table[r, t // P], t % P, : e.shape[-1]] = e[r, t]
    np.testing.assert_array_equal(pool[1, 1:], expected[1:])


@pytest.mark.parametrize("case", ["chunks_of_12", "heads20_context_edges", "heads20_64_ragged_rows"])
def test_both_forms_leave_the_same_bytes(case):
    kw = dict(lens=[27, 10], chunk=12) if case == "chunks_of_12" else dict(CASES[case], pages_per_buffer=6)
    _, _, a, *_ = _serve("xla", **kw)  # no halves there: the override is the kernel's
    _, _, b, *_ = _serve("pallas", **kw)
    np.testing.assert_array_equal(a[:, 1:], b[:, 1:])


def test_the_form_is_chosen_by_the_query_rows_of_a_key_tile():
    """``W * Hg < 128`` (a decode row's 20 heads, a verify window of 6) is the
    narrow form: one query tile, long halves in a ring of three, key tiles of
    two sizes. From 128 rows on (a window of 7, a prefill chunk of 128, 4
    heads x 32) the wide form keeps the ragged kernel's own tiles and its two
    halves."""
    from deepspeed_tpu.ops.transformer.decode_attention import _ragged_tiles
    from deepspeed_tpu.ops.transformer.latent_attention import _latent_tiles

    glm = dict(P=64, D=640, maxp=64, itemsize=2)  # GLM-4.7-Flash's pages: 64 entries of 640 lanes, 4,096 tokens a row
    assert _latent_tiles(20, 1, **glm) == (24, 12, 20, 3)  # halves of 1,536 keys; tiles of 768 and 1,536
    assert _latent_tiles(20, 6, **glm) == (24, 12, 120, 3)
    assert _latent_tiles(20, 1, **dict(glm, maxp=10)) == (10, 5, 20, 3)  # a table shorter than a half
    assert _latent_tiles(20, 1, **glm, pages_per_buffer=6) == (6, 3, 20, 3)  # the override is a half's pages
    assert _latent_tiles(20, 1, **glm, pages_per_buffer=1) == (1, 1, 20, 3)
    for Hg, W in ((20, 7), (20, 128), (4, 32), (128, 1)):
        for pages_per_buffer in (None, 2):
            assert _latent_tiles(Hg, W, **glm, pages_per_buffer=pages_per_buffer) == _ragged_tiles(1, Hg, W, 64, 640, 64, 2, pages_per_buffer)[:3] + (2,)


def test_a_shape_builds_its_call_once():
    """Two call sites of one shape, in two programs, trace the kernel's body
    once between them (a serving process has six such sites: its set-up);
    another width is another call."""
    from deepspeed_tpu.ops.transformer import latent_attention as module

    module._latent_call.cache_clear()
    attend = functools.partial(latent_paged_attention, value_lanes=128, scale=0.1, impl="pallas", interpret=True)

    def twice(q, new, pool, table, lens):
        o, pool = attend(q, new, pool, 0, table, lens, lens)
        return attend(q + o[..., :1], new, pool, 1, table, lens, lens)

    def operands(W):
        return jnp.zeros((3, W, 20, 160)), jnp.zeros((3, W, 160)), jnp.zeros((2, 7, P, 256)), jnp.zeros((3, 2), jnp.int32), jnp.ones((3,), jnp.int32)

    jax.make_jaxpr(twice)(*operands(1))
    jax.make_jaxpr(lambda *a: twice(*a)[0].sum())(*operands(1))
    assert (module._latent_call.cache_info().misses, module._latent_call.cache_info().hits) == (1, 3)
    jax.make_jaxpr(twice)(*operands(8))
    assert module._latent_call.cache_info().misses == 2


def test_bfloat16_pool_rounds_p_once():
    """In the served type the kernel takes ``p . v`` with ``p`` rounded to
    bfloat16 (the products with q are bfloat16 too): against float64 on the
    rounded inputs the result lies inside bfloat16's own band."""
    rs = np.random.RandomState(3)
    R, NH, D, Dv, n = 2, 4, 256, 128, 20
    q = jnp.asarray(rs.randn(R, 1, NH, D), jnp.bfloat16)
    e = jnp.asarray(rs.randn(R, n, D), jnp.bfloat16)
    pool = jnp.zeros((1, 1 + R * 3, P, D), jnp.bfloat16)
    table = np.stack([1 + r * 3 + np.arange(3) for r in range(R)]).astype(np.int32)
    call = jax.jit(functools.partial(latent_paged_attention, value_lanes=Dv, scale=D ** -0.5, impl="pallas", interpret=True))
    for t in range(n):
        o, pool = call(q, e[:, t : t + 1], pool, 0, jnp.asarray(table), jnp.full((R,), t + 1, jnp.int32), jnp.ones((R,), jnp.int32))
    ef = np.asarray(e, np.float32)
    s = np.einsum("rhd,rsd->rhs", np.asarray(q, np.float32)[:, 0], ef).astype(np.float64) * D ** -0.5
    p = np.exp(s - s.max(-1, keepdims=True))
    plain = np.einsum("rhs,rsd->rhd", p / p.sum(-1, keepdims=True), ef[..., :Dv])
    np.testing.assert_allclose(np.asarray(o, np.float32)[:, 0], plain, atol=2e-2)


def test_narrow_pages_are_refused_by_the_kernel_and_served_by_xla():
    q, e = jnp.zeros((1, 1, 2, 40)), jnp.zeros((1, 1, 40))
    pool, table, one = jnp.zeros((1, 3, P, 40)), jnp.ones((1, 2), jnp.int32), jnp.ones((1,), jnp.int32)
    with pytest.raises(NotImplementedError, match="whole lane tiles"):
        latent_paged_attention(q, e, pool, 0, table, one, one, value_lanes=32, scale=1.0, impl="pallas")
    o, _ = latent_paged_attention(q, e, pool, 0, table, one, one, value_lanes=32, scale=1.0, impl="xla")
    assert o.shape == (1, 1, 2, 32)
