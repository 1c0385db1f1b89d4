"""The ragged paged kernel's static options (``window``, ``sinks``, a value
width of its own, a key pool wider than the head) against a plain masked
softmax over a row's whole history, and its defaults against what they were.

The window layers' pages are a RING: table slot ``i`` of a row is page
``i % n`` of the row's own ``n``. The rows below are stepped through the
kernel as the server steps them (chunks on the chunk grid, then single
tokens), so that positions wrap round the ring several times and the kernel
reads what earlier calls left there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import decode_attention
from deepspeed_tpu.ops.transformer.paged_attention import ragged_paged_attention

P = 8


def _plain(q, k, v, window, sinks, scale):
    """``q`` [T, NH, D] against the sequence's own ``k`` [T, NKV, D] and ``v``
    [T, NKV, Dv]: masks from positions, the sink as an appended column."""
    T, NH, _ = q.shape
    G = NH // k.shape[1]
    k, v = np.repeat(k, G, axis=1), np.repeat(v, G, axis=1)
    s = np.einsum("thd,shd->hts", q, k).astype(np.float64) * scale
    i, j = np.arange(T)[:, None], np.arange(T)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    s = np.where(seen, s, -np.inf)
    if sinks is not None:
        s = np.concatenate([s, np.broadcast_to(np.asarray(sinks, np.float64)[:, None, None], (NH, T, 1))], axis=-1)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hts,shd->thd", p[..., :T], v)


def _serve(impl, lens, chunk, window, sinks, NH=4, NKV=2, D=16, Dv=16, Dpool=None, ring=None, seed=0, steps=None,
           pages_per_buffer=None, rows_per_step=None):
    """Every sequence of ``lens`` through ``ragged_paged_attention``: whole
    chunks of ``chunk`` from position 0 up to ``steps[r]`` tokens (default:
    all but the last three), then one token a call. Returns the served
    outputs and the plain ones, [T, NH, Dv] a sequence."""
    rs = np.random.RandomState(seed)
    R, T = len(lens), max(lens)
    Dpool = Dpool or D
    ring = ring or (-(-chunk // P) + -(-(window - 1) // P) if window else -(-T // P))
    maxp = -(-T // P)
    q = rs.randn(R, T, NH, D).astype(np.float32)
    k = rs.randn(R, T, NKV, D).astype(np.float32)
    v = rs.randn(R, T, NKV, Dv).astype(np.float32)
    kp = jnp.zeros((1, 1 + R * ring, NKV, P, Dpool), jnp.float32)
    vp = jnp.zeros((1, 1 + R * ring, NKV, P, Dv), jnp.float32)
    table = np.stack([1 + r * ring + np.arange(maxp) % ring for r in range(R)]).astype(np.int32)
    call = jax.jit(functools.partial(ragged_paged_attention, impl=impl, window=window, scale=D ** -0.5))
    if pages_per_buffer or rows_per_step:  # the kernel itself, with short halves or blocks of rows
        call = jax.jit(functools.partial(
            decode_attention.ragged_paged_attention, interpret=True, window=window, scale=D ** -0.5,
            pages_per_buffer=pages_per_buffer, rows_per_step=rows_per_step,
        ))
    out = np.zeros((R, T, NH, Dv), np.float32)
    done = np.zeros(R, np.int64)
    steps = steps or [n - 3 for n in lens]
    while (done < lens).any():
        q_lens = np.array([0 if d >= n else (min(chunk, s - d) if d < s else 1) for d, n, s in zip(done, lens, steps)])
        W = chunk if (q_lens > 1).any() else 1
        win = lambda a: np.stack([np.pad(a[r, done[r] : done[r] + q_lens[r]], ((0, W - q_lens[r]), (0, 0), (0, 0))) for r in range(R)])
        o, kp, vp = call(
            jnp.asarray(win(q)), jnp.asarray(win(k)), jnp.asarray(win(v)), kp, vp, 0, jnp.asarray(table),
            jnp.asarray(np.where(q_lens > 0, done + q_lens, 0), jnp.int32), jnp.asarray(q_lens, jnp.int32), sinks=sinks,
        )
        for r in range(R):
            out[r, done[r] : done[r] + q_lens[r]] = np.asarray(o)[r, : q_lens[r]]
        done += q_lens
    plain = [_plain(q[r, :n], k[r, :n], v[r, :n], window, sinks, D ** -0.5) for r, n in enumerate(lens)]
    return [out[r, :n] for r, n in enumerate(lens)], plain


CASES = {
    # a decode row deep inside a wide window (window 40 of a 21-token row: nothing is cut)
    "row_shorter_than_window": dict(lens=[21, 9], chunk=16, window=40, sinks=None),
    # chunks that straddle the window's edge, then decode several times round a ring of 4 pages
    "chunks_straddle_the_edge": dict(lens=[77, 50, 3], chunk=16, window=16, sinks=None),
    "window_one_key_past_a_page": dict(lens=[60, 41], chunk=16, window=9, sinks=None),
    "sinks_alone": dict(lens=[30, 12], chunk=16, window=None, sinks=[0.5, -1.0, 2.0, 0.0]),
    "window_and_sinks": dict(lens=[70, 33], chunk=16, window=12, sinks=[1.5, -0.5, 0.25, 3.0]),
    # a value head narrower than the key head, the key pool wider than the head (zero lanes)
    "value_width_of_its_own": dict(lens=[45, 20], chunk=16, window=10, sinks=[0.1, 0.2, 0.3, 0.4], D=24, Dv=16, Dpool=32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_xla_form_against_a_plain_masked_softmax(case):
    kw = dict(CASES[case])
    kw["sinks"] = None if kw["sinks"] is None else jnp.asarray(kw["sinks"], jnp.float32)
    served, plain = _serve("xla", **kw)
    for a, b in zip(served, plain):
        # float32 throughout; the orders of the sums differ
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pages_per_buffer", [None, 1, 2])
@pytest.mark.parametrize("case", ["chunks_straddle_the_edge", "window_and_sinks", "row_shorter_than_window"])
def test_kernel_against_a_plain_masked_softmax(case, pages_per_buffer):
    """The Pallas kernel (interpreted), at lane-whole widths: a key head of
    192 in a pool of 256 lanes, values of 128, two KV heads of two query
    heads each; with halves of one and two pages, so that a walk that starts
    past page 0 spans several."""
    kw = dict(CASES[case], D=192, Dv=128, Dpool=256, steps=None, pages_per_buffer=pages_per_buffer)
    kw["lens"] = [min(n, 40) for n in kw["lens"]]
    kw["sinks"] = None if kw["sinks"] is None else jnp.asarray(kw["sinks"], jnp.float32)
    served, plain = _serve("pallas", **kw)
    for a, b in zip(served, plain):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_a_ring_one_page_short_is_refused_by_the_arithmetic():
    """The ring holds ``ceil(chunk / P) + ceil((window - 1) / P)`` pages
    because chunks start on page boundaries; with one page fewer a chunk's
    walk overwrites a page it still has to read, and the result is wrong.
    Held so that nobody shrinks the ring."""
    kw = dict(CASES["chunks_straddle_the_edge"], sinks=None)
    served, plain = _serve("xla", ring=3, **kw)
    assert max(np.abs(a - b).max() for a, b in zip(served, plain)) > 1e-2


def test_defaults_trace_to_the_jaxpr_they_did():
    """With no window, no sinks and one width the kernel's body is the one
    every other model traces: the same equations, whatever the new
    arguments' defaults. Compared with a call that names the defaults, and
    by the count of equations against a windowed body's."""
    R, W, NH, NKV, D = 2, 1, 4, 2, 128
    q = jnp.zeros((R, W, NH, D), jnp.bfloat16)
    kv = jnp.zeros((R, W, NKV, D), jnp.bfloat16)
    pool = jnp.zeros((1, 9, NKV, P, D), jnp.bfloat16)
    table = jnp.zeros((R, 4), jnp.int32)
    lens = jnp.ones((R,), jnp.int32)

    def text(**kw):
        fn = functools.partial(decode_attention.ragged_paged_attention, interpret=False, **kw)
        return str(jax.make_jaxpr(fn)(q, kv, kv, pool, pool, 0, table, lens, lens))

    assert text() == text(window=None, sinks=None)
    assert text() != text(window=16)
    assert "sub" in text(window=16)


# One call of the kernel on rows deep in their contexts, a block of rows a grid
# step against a row a step: ``lens`` are the keys a row holds after the call
# (0: a dead row), ``new`` the tokens a live row writes (1: a decode row).
BLOCKS = {
    "dead_rows_in_the_middle_and_at_the_end": dict(lens=[37, 0, 0, 21, 58, 9, 0, 0], window=16),
    "rows_no_multiple_of_a_block": dict(lens=[33, 70, 12, 0, 49, 26], window=16),
    # rings of 3 pages a row: a row's walk starts on the ring's last page and ends on its first
    "ring_wraps_inside_a_block": dict(lens=[25, 49, 73, 95, 24, 48], window=12),
    # the new key alone on its page, the page before it full
    "new_key_opens_a_fresh_page": dict(lens=[41, 17, 65, 33, 1], window=16),
    "verify_rows_of_three": dict(lens=[37, 22, 0, 64, 11], window=16, W=3, new=[3, 1, 0, 2, 3]),
    "sinks_on_and_a_value_width_of_its_own": dict(lens=[45, 20, 72], window=10, sinks=[0.1, 0.2, 0.3, 0.4], D=192, Dv=128, Dpool=256),
    # the two window cells' geometry: pages of 64 in bfloat16, whose sublane tile (16 rows) is what a write-back moves
    "window_128_pages_of_64": dict(lens=[300, 129, 0, 64, 193, 577], window=128, page=64, dtype=jnp.bfloat16),
    "window_512_pages_of_64": dict(lens=[1100, 513, 700], window=512, page=64, dtype=jnp.bfloat16),
}


@functools.lru_cache(maxsize=None)
def _one_call(case, rows_per_step):
    """``(o, k_pool, v_pool, live)`` of the case's one call with ``rows_per_step`` rows a grid
    step, or of XLA's scatter + gather (``rows_per_step`` None)."""
    kw = dict(BLOCKS[case])
    lens, window, W, page = np.asarray(kw["lens"]), kw["window"], kw.get("W", 1), kw.get("page", P)
    new = np.asarray(kw.get("new", (lens > 0).astype(int)))
    D, Dv, dtype = kw.get("D", 128), kw.get("Dv", 128), kw.get("dtype", jnp.float32)
    sinks = None if kw.get("sinks") is None else jnp.asarray(kw["sinks"], jnp.float32)
    R, NH, NKV = len(lens), 4, 2
    ring = -(-W // page) + -(-(window - 1) // page)
    maxp = -(-int(lens.max()) // page)
    rs = np.random.RandomState(len(case))
    q, k, v = (jnp.asarray(rs.randn(R, W, heads, lanes), dtype) for heads, lanes in ((NH, D), (NKV, D), (NKV, Dv)))
    kp, vp = (jnp.asarray(rs.randn(1, 1 + R * ring, NKV, page, lanes), dtype) for lanes in (kw.get("Dpool", D), Dv))
    table = jnp.asarray(np.stack([1 + r * ring + np.arange(maxp) % ring for r in range(R)]), jnp.int32)
    args = (q, k, v, kp, vp, 0, table, jnp.asarray(lens, jnp.int32), jnp.asarray(new, jnp.int32))
    if rows_per_step is None:
        o, kp, vp = jax.jit(functools.partial(ragged_paged_attention, impl="xla", window=window, scale=D ** -0.5))(*args, sinks=sinks)
    else:
        o, kp, vp = jax.jit(functools.partial(
            decode_attention.ragged_paged_attention, interpret=True, window=window, scale=D ** -0.5, rows_per_step=rows_per_step,
        ))(*args, sinks=sinks)
    live = np.arange(W)[None, :] < new[:, None]
    return np.asarray(o, np.float32), np.asarray(kp, np.float32), np.asarray(vp, np.float32), live


_EIGHT = ["dead_rows_in_the_middle_and_at_the_end", "rows_no_multiple_of_a_block", "window_128_pages_of_64"]


@pytest.mark.parametrize("case,rows_per_step", [(case, 4) for case in sorted(BLOCKS)] + [(case, 8) for case in _EIGHT])
def test_a_block_of_rows_a_grid_step_gives_what_a_row_a_step_gives(case, rows_per_step):
    """``_ragged_block_kernel`` (blocks that dead rows fill, that a ring wraps
    in, that R is no multiple of) bit for bit against a row a grid step, its
    walk in halves: outputs, and every page but the trash page. The merged
    slab is all of a page that a write-back moves, so the rest of the page
    must be what it was."""
    o, kp, vp, live = _one_call(case, rows_per_step)
    want_o, want_k, want_v, _ = _one_call(case, 1)
    np.testing.assert_array_equal(o, want_o)
    assert (o[~live.any(axis=1)] == 0).all()  # dead rows: exact zeros
    np.testing.assert_array_equal(kp[:, 1:], want_k[:, 1:])
    np.testing.assert_array_equal(vp[:, 1:], want_v[:, 1:])


@pytest.mark.parametrize("case", ["window_128_pages_of_64", "window_512_pages_of_64"])
def test_a_block_of_rows_at_the_cells_geometry_against_xla(case):
    """Pages of 64 in bfloat16, windows of 128 and 512 keys: the block form
    against XLA's scatter + gather, outputs to bfloat16's rounding and the
    pools bit for bit."""
    o, kp, vp, live = _one_call(case, 4)
    want_o, want_k, want_v, _ = _one_call(case, None)
    np.testing.assert_allclose(o[live], want_o[live], rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(kp[:, 1:], want_k[:, 1:])
    np.testing.assert_array_equal(vp[:, 1:], want_v[:, 1:])


@pytest.mark.parametrize("case", ["chunks_straddle_the_edge", "window_and_sinks"])
def test_blocks_of_rows_served_token_by_token_against_a_plain_masked_softmax(case):
    """Every sequence decoded from its first token on through blocks of two
    rows: each call reads what the calls before it wrote back, a slab at a
    time, several times round the ring; rows die as their sequences end."""
    kw = dict(CASES[case], D=192, Dv=128, Dpool=256, chunk=1, rows_per_step=2)
    kw["lens"] = [min(n, 40) for n in kw["lens"]]
    kw["steps"] = [0] * len(kw["lens"])
    kw["sinks"] = None if kw["sinks"] is None else jnp.asarray(kw["sinks"], jnp.float32)
    served, plain = _serve("pallas", **kw)
    for a, b in zip(served, plain):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_the_block_form_is_for_narrow_rows_a_window_bounds():
    """``_ragged_block`` from the shapes alone: the two window cells' decode
    rows go ``_BLOCK_ROWS`` a grid step, in slots of whole key tiles; a
    prefill chunk's rows, a layer with no window and a window whose ring
    would not fit ``_RING_BYTES`` keep one row a step."""
    block = functools.partial(decode_attention._ragged_block, 8, itemsize=2)
    laguna = dict(Hg=9, P=64, D=128, Dv=128, CK=4, window=512)
    mimo = dict(Hg=8, P=64, D=256, Dv=128, CK=2, window=128)
    assert block(W=1, **laguna) == (decode_attention._BLOCK_ROWS, 12)  # 9 pages, in key tiles of 4
    assert block(W=1, **mimo) == (decode_attention._BLOCK_ROWS, 4)  # 3 pages, in key tiles of 2
    assert block(W=128, **laguna) == block(W=128, **mimo) == (1, 0)
    assert block(W=1, **dict(laguna, window=None)) == (1, 0)
    assert block(W=1, **dict(laguna, window=4096)) == (1, 68)
    assert block(W=1, rows_per_step=3, **mimo) == (3, 4)
