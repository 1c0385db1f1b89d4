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
           pages_per_buffer=None):
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
    if pages_per_buffer:  # the kernel itself, with short halves
        call = jax.jit(functools.partial(
            decode_attention.ragged_paged_attention, interpret=True, window=window, scale=D ** -0.5,
            pages_per_buffer=pages_per_buffer,
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
