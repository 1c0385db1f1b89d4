"""The state-space recurrence's forms (``ops/transformer/state_space.py``)
against one another: the chunk form against the token-by-token scan (one
chunk, several chunks, a prompt of 2.5 chunks carried chunk by chunk with its
state), the decode kernel (interpreted) and its XLA form against ``ssd_step``
behind the plain convolution, in place on both pools, dead and fresh rows.

Float32 throughout: the forms differ by the order of their sums; readings are
1e-5 on outputs of magnitude ~30 (the chunk form: an exponential of a sum
against a product of exponentials) and 1e-6 for one token.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import state_space as ss

NH, P, N, K = 4, 64, 128, 4
C = NH * P + 2 * N


def _inputs(seed, B, T):
    rng = np.random.default_rng(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x, Bm, Cm = f32(rng.standard_normal((B, T, NH, P))), f32(rng.standard_normal((B, T, N))), f32(rng.standard_normal((B, T, N)))
    dt = f32(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (B, T, NH))))
    A, D = -f32(rng.uniform(1.0, 16.0, NH)), f32(rng.standard_normal(NH))
    return x, Bm, Cm, dt, A, D, f32(rng.standard_normal((B, NH, P, N)))


def _close(a, b, tol):
    assert float(jnp.abs(a - b).max()) < tol * max(1.0, float(jnp.abs(b).max()))


@pytest.mark.parametrize("T,chunk", [(48, 64), (128, 128), (200, 64), (7, 128)], ids=["part_of_a_chunk", "one_chunk", "chunks_and_a_tail", "a_few_tokens"])
def test_the_chunk_form_is_the_recurrence(T, chunk):
    x, Bm, Cm, dt, A, D, S0 = _inputs(T, 2, T)
    y, S = ss.ssd_recurrent(x, Bm, Cm, dt, A, D, S0)
    y2, S2 = ss.ssd_chunked(x, Bm, Cm, dt, A, D, S0, chunk=chunk)
    assert y2.shape == y.shape == (2, T, NH, P)
    _close(y2, y, 2e-6)
    _close(S2, S, 2e-6)


def test_a_prompt_of_two_and_a_half_chunks_carries_its_state():
    """What the server does with a prompt: a chunk a step, each from the state
    the step before left; the last chunk half full, its dead positions (dt 0)
    leaving the state alone."""
    T, chunk = 160, 64
    x, Bm, Cm, dt, A, D, S0 = _inputs(3, 1, T)
    y, S = ss.ssd_recurrent(x, Bm, Cm, dt, A, D, S0)
    got, state = [], S0
    for start in range(0, T, chunk):
        part = [jnp.pad(a[:, start : start + chunk], [(0, 0), (0, max(0, start + chunk - T))] + [(0, 0)] * (a.ndim - 2)) for a in (x, Bm, Cm, dt)]
        y_part, state = ss.ssd_chunked(*part, A, D, state, chunk=chunk)
        got.append(y_part)
    _close(jnp.concatenate(got, axis=1)[:, :T], y, 2e-6)
    _close(state, S, 2e-6)


def test_a_dead_position_leaves_the_state_as_it_is():
    x, Bm, Cm, dt, A, D, S0 = _inputs(5, 1, 16)
    _, S = ss.ssd_chunked(x, Bm, Cm, jnp.zeros_like(dt), A, D, S0)
    assert np.array_equal(np.asarray(S), np.asarray(S0))


def _decode_case(seed, dtype, R=5, L=2):
    rng = np.random.default_rng(seed)
    rows = ss.tail_rows(C)
    pool = jnp.asarray(rng.standard_normal((L, R + 2, NH, P, N)), jnp.float32)
    tails = jnp.asarray(rng.standard_normal((L, R + 2, K - 1, rows, ss.LANES)), dtype).at[:, :, :, C // ss.LANES :].set(0)
    xbc = jnp.asarray(rng.standard_normal((R, C)), dtype)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (R, NH))), jnp.float32)
    w, b = jnp.asarray(rng.standard_normal((K, C)) * 0.5, dtype), jnp.asarray(rng.standard_normal(C) * 0.1, dtype)
    A, D = -jnp.asarray(rng.uniform(1.0, 16.0, NH), jnp.float32), jnp.asarray(rng.standard_normal(NH), jnp.float32)
    slots = jnp.asarray([3, 0, 5, 1, 2], jnp.int32)
    live, fresh = jnp.asarray([1, 1, 0, 1, 1], bool), jnp.asarray([0, 1, 0, 0, 0], bool)
    return (xbc, dt, w, b, A, D, pool, tails, 1, slots, live, fresh), rows


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_the_decode_kernel_is_one_step_in_place(impl, dtype):
    """Row r's state and tail at ``slots[r]`` of layer 1: the convolution over
    (tail, token) with its bias and SiLU, ``ssd_step``, the tail shifted; a
    fresh row starts from zeros whatever its slot holds; a dead row leaves
    zeros in the spare slot and touches nobody's; layer 0 and the slots of no
    row keep their bytes."""
    args, rows = _decode_case(1, dtype)
    xbc, dt, w, b, A, D, pool, tails, layer, slots, live, fresh = args
    y, new_pool, new_tails = ss.ssd_decode(*args, impl=impl)
    assert y.shape == (5, NH * P) and y.dtype == jnp.float32 and new_pool.dtype == jnp.float32 and new_tails.dtype == dtype
    f32 = lambda a: np.asarray(a, np.float32)
    for r in range(5):
        s = int(slots[r])
        if not bool(live[r]):
            assert not f32(y[r]).any() and not f32(new_pool[layer, -1]).any() and not f32(new_tails[layer, -1]).any()
            assert np.array_equal(f32(new_pool[layer, s]), f32(pool[layer, s]))  # the slot it named is nobody's business
            continue
        tail = jnp.zeros((K - 1, C), dtype) if bool(fresh[r]) else tails[layer, s, :, : C // ss.LANES].reshape(K - 1, C)
        ext = jnp.concatenate([tail, xbc[r : r + 1]]).astype(jnp.float32)
        conv = jax.nn.silu(b.astype(jnp.float32) + sum(w[j].astype(jnp.float32) * ext[j] for j in range(K)))
        S0 = jnp.zeros((NH, P, N)) if bool(fresh[r]) else pool[layer, s]
        want, S = ss.ssd_step(S0, conv[: NH * P].reshape(NH, P), conv[NH * P : NH * P + N], conv[NH * P + N :], dt[r], A, D)
        _close(y[r], want.reshape(-1), 2e-6)
        _close(new_pool[layer, s], S, 2e-6)
        assert np.array_equal(f32(new_tails[layer, s, :, : C // ss.LANES]).reshape(K - 1, C), f32(ext[1:].astype(dtype)))
        assert not f32(new_tails[layer, s, :, C // ss.LANES :]).any()  # the rows past the channels stay zeros
    assert np.array_equal(f32(new_pool[0]), f32(pool[0])) and np.array_equal(f32(new_tails[0]), f32(tails[0]))
    assert np.array_equal(f32(new_pool[layer, 4]), f32(pool[layer, 4]))  # no row's slot


def test_the_kernel_and_the_xla_form_leave_the_same_bytes():
    args, _ = _decode_case(2, jnp.bfloat16)
    a, b = ss.ssd_decode(*args, impl="xla"), ss.ssd_decode(*args, impl="pallas_interpret")
    _close(a[0], b[0], 2e-6)
    _close(a[1], b[1], 2e-6)
    assert np.array_equal(np.asarray(a[2], np.float32), np.asarray(b[2], np.float32))


def test_two_tokens_through_the_kernel_are_two_tokens_of_the_chunk_form():
    """A row decoded twice, the second token on the state and tail the first
    left: the chunk form's outputs and state over the same two tokens."""
    args, rows = _decode_case(4, jnp.float32, L=1)
    xbc, dt, w, b, A, D, pool, tails, _, slots, live, fresh = args
    rng = np.random.default_rng(9)
    xbc2 = jnp.asarray(rng.standard_normal(xbc.shape), jnp.float32)
    dt2 = dt[::-1]
    y1, p1, t1 = ss.ssd_decode(xbc, dt, w, b, A, D, pool, tails, 0, slots, live, fresh, impl="pallas_interpret")
    y2, p2, _ = ss.ssd_decode(xbc2, dt2, w, b, A, D, p1, t1, 0, slots, live, jnp.zeros_like(fresh), impl="pallas_interpret")
    r, s = 3, int(slots[3])  # a live row that is not fresh
    tail = tails[0, s, :, : C // ss.LANES].reshape(K - 1, C)
    ext = jnp.concatenate([tail, xbc[r : r + 1], xbc2[r : r + 1]])
    conv = jax.nn.silu(b + sum(w[j] * ext[j : j + 2] for j in range(K)))  # [2, C]
    xs, Bm, Cm = conv[:, : NH * P].reshape(1, 2, NH, P), conv[None, :, NH * P : NH * P + N], conv[None, :, NH * P + N :]
    want, S = ss.ssd_chunked(xs, Bm, Cm, jnp.stack([dt[r], dt2[r]])[None], A, D, pool[0, s][None])
    _close(jnp.stack([y1[r], y2[r]]), want.reshape(2, -1), 2e-6)
    _close(p2[0, s], S[0], 2e-6)


@pytest.mark.parametrize("what", ["state_of_64", "heads_of_48", "unknown_impl"])
def test_what_the_kernel_cannot_tile_is_refused_by_name(what):
    args, _ = _decode_case(1, jnp.float32)
    xbc, dt, w, b, A, D, pool, tails, layer, slots, live, fresh = args
    if what == "unknown_impl":
        with pytest.raises(ValueError, match="unknown ssd_decode impl"):
            ss.ssd_decode(*args, impl="mosaic")
        return
    if what == "state_of_64":  # C = 4 x 64 + 2 x 64 = 384
        pool, xbc, w, b = pool[..., :64], xbc[:, :384], w[:, :384], b[:384]
    else:  # heads of 48 divide no lane tile
        pool, xbc, w, b = pool[:, :, :, :48], xbc[:, : 4 * 48 + 256], w[:, : 4 * 48 + 256], b[: 4 * 48 + 256]
    with pytest.raises(ValueError, match="ssd_decode's kernel needs"):
        ss.ssd_decode(xbc, dt, w, b, A, D, pool, tails, layer, slots, live, fresh, impl="pallas_interpret")


def test_tail_rows_are_whole_sublane_tiles():
    assert (ss.tail_rows(4352), ss.tail_rows(384), ss.tail_rows(2048), ss.tail_rows(2176)) == (48, 16, 16, 32)
