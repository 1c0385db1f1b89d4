"""The state-space recurrence with GROUPS of ``B`` and ``C``
(``ops/transformer/state_space.py``; Nemotron-H has eight): every form at
``G`` = 1, 2 and 8 against the one-group recurrence run a group's heads at a
time with that group's ``B`` and ``C`` (which is what "head n reads group
``n // (NH / G)``" says), the forms against one another, and at ``G`` = 1 bit
for bit what the one-group code gave before groups existed (``_parent_*``
below are that code, kept here as it was).

Float32 throughout; the forms differ by the order of their sums (1e-5 on
outputs of magnitude ~30 for the chunk form, 1e-6 for one token).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer import state_space as ss

NH, P, N, K = 16, 64, 128, 4
GROUPS = [1, 2, 8]
_HIGHEST = jax.lax.Precision.HIGHEST


def _inputs(seed, B, T, G):
    rng = np.random.default_rng(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(rng.standard_normal((B, T, NH, P)))
    Bm, Cm = f32(rng.standard_normal((B, T, G, N))), f32(rng.standard_normal((B, T, G, N)))
    dt = f32(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (B, T, NH))))
    A, D = -f32(rng.uniform(1.0, 16.0, NH)), f32(rng.standard_normal(NH))
    return x, Bm, Cm, dt, A, D, f32(rng.standard_normal((B, NH, P, N)))


def _close(a, b, tol):
    assert float(jnp.abs(a - b).max()) < tol * max(1.0, float(jnp.abs(b).max()))


def _a_group_at_a_time(x, Bm, Cm, dt, A, D, state):
    """The one-group recurrence over each group's heads with the group's own ``B`` and ``C``."""
    G = Bm.shape[2]
    per = NH // G
    ys, states = [], []
    for g in range(G):
        heads = slice(g * per, (g + 1) * per)
        y, S = ss.ssd_recurrent(x[:, :, heads], Bm[:, :, g], Cm[:, :, g], dt[..., heads], A[heads], D[heads], state[:, heads])
        ys.append(y)
        states.append(S)
    return jnp.concatenate(ys, axis=2), jnp.concatenate(states, axis=1)


# --- the one-group code as it stood before PR 59 ----------------------------------


def _parent_step(S, x, B, C, dt, A, D):
    S = S * jnp.exp(dt * A)[..., None, None] + (dt[..., None] * x)[..., None] * B[..., None, None, :]
    return jnp.sum(S * C[..., None, None, :], axis=-1) + D[:, None] * x, S


def _parent_chunk(A, D, S0, t):
    x, Bm, Cm, dt = t
    T = x.shape[2]
    g = jnp.cumsum(dt * A[:, None], axis=-1)
    seen = jnp.tril(jnp.ones((T, T), bool))
    L = jnp.exp(jnp.where(seen, g[..., :, None] - g[..., None, :], -jnp.inf))
    CB = jnp.einsum("btn,bin->bti", Cm, Bm, precision=_HIGHEST)
    dtx = dt[..., None] * x
    eg = jnp.exp(g)
    y = jnp.einsum("bhti,bhip->bhtp", L * CB[:, None], dtx, precision=_HIGHEST)
    y = y + eg[..., None] * jnp.einsum("btn,bhpn->bhtp", Cm, S0, precision=_HIGHEST) + D[:, None, None] * x
    to_end = jnp.exp(g[..., -1:] - g)
    S = eg[..., -1, None, None] * S0 + jnp.einsum("bhip,bin->bhpn", to_end[..., None] * dtx, Bm, precision=_HIGHEST)
    return S, y


# --- the recurrence and the chunk form ---------------------------------------------


@pytest.mark.parametrize("G", GROUPS)
def test_the_recurrence_reads_a_heads_own_group(G):
    args = _inputs(1, 2, 9, G)
    y, S = ss.ssd_recurrent(*args)
    want_y, want_S = _a_group_at_a_time(*args)
    _close(y, want_y, 1e-6)
    _close(S, want_S, 1e-6)
    if G > 1:  # and not group 0 for every head: that is another answer
        wrong, _ = ss.ssd_recurrent(args[0], args[1][:, :, 0], args[2][:, :, 0], *args[3:])
        assert float(jnp.abs(wrong - y).max()) > 1.0


@pytest.mark.parametrize("T,chunk", [(48, 64), (200, 64)], ids=["part_of_a_chunk", "chunks_and_a_tail"])
@pytest.mark.parametrize("G", GROUPS)
def test_the_chunk_form_is_the_recurrence_at_every_group_count(G, T, chunk):
    args = _inputs(2, 2, T, G)
    y, S = ss.ssd_chunked(*args, chunk=chunk)
    want_y, want_S = ss.ssd_recurrent(*args)
    _close(y, want_y, 2e-5)
    _close(S, want_S, 2e-5)


def test_one_group_named_as_a_group_is_the_ungrouped_form():
    x, Bm, Cm, dt, A, D, state = _inputs(3, 2, 40, 1)
    for form in (ss.ssd_recurrent, ss.ssd_chunked):
        y, S = form(x, Bm, Cm, dt, A, D, state)
        want_y, want_S = form(x, Bm[:, :, 0], Cm[:, :, 0], dt, A, D, state)
        _close(y, want_y, 1e-6)
        _close(S, want_S, 1e-6)


def test_heads_that_are_no_whole_number_of_groups_are_refused():
    x, Bm, Cm, dt, A, D, state = _inputs(3, 1, 4, 3)
    for form in (ss.ssd_recurrent, ss.ssd_chunked):
        with pytest.raises(ValueError, match="whole number"):
            form(x, Bm, Cm, dt, A, D, state)


@pytest.mark.parametrize("form", ["step", "recurrent", "chunked"])
def test_at_one_group_the_forms_give_bit_for_bit_what_they_gave(form):
    x, Bm, Cm, dt, A, D, state = _inputs(4, 2, 70, 1)
    Bm, Cm = Bm[:, :, 0], Cm[:, :, 0]
    same = lambda a, b: np.array_equal(np.asarray(a), np.asarray(b))
    if form == "step":
        got, want = ss.ssd_step(state, x[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0], A, D), _parent_step(state, x[:, 0], Bm[:, 0], Cm[:, 0], dt[:, 0], A, D)
    elif form == "recurrent":

        def parent(S, t):
            y, S = _parent_step(S, *t, A, D)
            return S, y

        S, y = jax.lax.scan(parent, state, tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt)))
        got, want = ss.ssd_recurrent(x, Bm, Cm, dt, A, D, state), (jnp.moveaxis(y, 0, 1), S)
    else:  # one chunk of the whole window: ``_chunk`` against the parent's, operand for operand
        t = (jnp.moveaxis(x, 2, 1), Bm, Cm, jnp.moveaxis(dt, 2, 1))
        got, want = ss._chunk(A, D, state, t), _parent_chunk(A, D, state, t)
    assert same(got[0], want[0]) and same(got[1], want[1])


# --- one token a row, in place on the pools ----------------------------------------


def _decode_case(seed, G, R=4, L=2):
    rng = np.random.default_rng(seed)
    C = NH * P + 2 * G * N
    rows = ss.tail_rows(C)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    pool = f32(rng.standard_normal((L, R + 3, NH, P, N)))
    tails = f32(rng.standard_normal((L, R + 3, K - 1, rows, ss.LANES))).at[:, :, :, C // ss.LANES :].set(0)
    xbc = f32(rng.standard_normal((R, C)))
    dt = f32(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (R, NH))))
    w, b = f32(rng.standard_normal((K, C)) * 0.5), f32(rng.standard_normal(C) * 0.1)
    A, D = -f32(rng.uniform(1.0, 16.0, NH)), f32(rng.standard_normal(NH))
    slots = jnp.asarray([3, 0, 5, 1], jnp.int32)
    live, fresh = jnp.asarray([1, 1, 0, 1], bool), jnp.asarray([0, 1, 0, 0], bool)
    return (xbc, dt, w, b, A, D, pool, tails, 1, slots, live, fresh), C


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("G", GROUPS)
def test_the_decode_forms_are_one_grouped_step_in_place(G, impl):
    """The convolution over all ``NH P + 2 G N`` channels together, then a
    head's update and read-out with its own group's ``B`` and ``C``; the tail
    shifted; a fresh row from zeros, a dead row nobody's; other layers and
    slots keep their bytes."""
    args, C = _decode_case(5, G)
    xbc, dt, w, b, A, D, pool, tails, layer, slots, live, fresh = args
    y, new_pool, new_tails = ss.ssd_decode(*args, impl=impl)
    assert y.shape == (4, NH * P)
    f32 = lambda a: np.asarray(a, np.float32)
    for r in range(4):
        s = int(slots[r])
        if not bool(live[r]):
            assert not f32(y[r]).any() and not f32(new_pool[layer, -1]).any() and np.array_equal(f32(new_pool[layer, s]), f32(pool[layer, s]))
            continue
        tail = jnp.zeros((K - 1, C)) if bool(fresh[r]) else tails[layer, s, :, : C // ss.LANES].reshape(K - 1, C)
        ext = jnp.concatenate([tail, xbc[r : r + 1]])
        conv = jax.nn.silu(b + sum(w[j] * ext[j] for j in range(K)))
        S0 = jnp.zeros((NH, P, N)) if bool(fresh[r]) else pool[layer, s]
        Bg, Cg = conv[NH * P : NH * P + G * N].reshape(G, N), conv[NH * P + G * N :].reshape(G, N)
        x = conv[: NH * P].reshape(NH, P)
        per = NH // G
        for g in range(G):  # a group's heads through the ONE-group step with the group's B and C
            heads = slice(g * per, (g + 1) * per)
            want, S = _parent_step(S0[heads], x[heads], Bg[g], Cg[g], dt[r, heads], A[heads], D[heads])
            _close(y[r].reshape(NH, P)[heads], want, 2e-6)
            _close(new_pool[layer, s, heads], S, 2e-6)
        assert np.array_equal(f32(new_tails[layer, s, :, : C // ss.LANES]).reshape(K - 1, C), f32(ext[1:]))
    assert np.array_equal(f32(new_pool[0]), f32(pool[0])) and np.array_equal(f32(new_tails[0]), f32(tails[0]))


@pytest.mark.parametrize("G", GROUPS)
def test_the_kernel_and_the_gather_scatter_form_agree(G):
    args, _ = _decode_case(6, G)
    a, b = ss.ssd_decode(*args, impl="xla"), ss.ssd_decode(*args, impl="pallas_interpret")
    _close(a[0], b[0], 2e-6)
    _close(a[1], b[1], 2e-6)
    assert np.array_equal(np.asarray(a[2]), np.asarray(b[2]))


def test_at_one_group_the_gather_scatter_form_gives_bit_for_bit_what_it_gave():
    args, C = _decode_case(7, 1)
    xbc, dt, w, b, A, D, pool, tails, layer, slots, live, fresh = args
    y, new_pool, _ = ss.ssd_decode(*args, impl="xla")
    # the parent's own lines (ssd_decode's XLA branch), with its step
    R, NT, NS = xbc.shape[0], C // ss.LANES, pool.shape[1]
    slots_ = jnp.where(live, slots, NS - 1)
    fresh_ = fresh | ~live
    zeroed = lambda a: jnp.where(fresh_.reshape((-1,) + (1,) * (a.ndim - 1)), 0, a)
    tail = zeroed(tails[layer, slots_])[:, :, :NT].reshape(R, -1, C)
    conv = ss.decode_conv(w, b, [tail[:, j] for j in range(tail.shape[1])] + [xbc])
    x, Bm, Cm = conv[:, : NH * P].reshape(R, NH, P), conv[:, NH * P : NH * P + N], conv[:, NH * P + N :]
    want, S = _parent_step(zeroed(pool[layer, slots_]).astype(jnp.float32), x, Bm, Cm, dt, A, D)
    alive = lambda a: jnp.where(live.reshape((-1,) + (1,) * (a.ndim - 1)), a, 0)
    assert np.array_equal(np.asarray(y), np.asarray(alive(want).reshape(R, NH * P)))
    assert np.array_equal(np.asarray(new_pool), np.asarray(pool.at[layer, slots_].set(alive(S))))


@pytest.mark.parametrize("what", ["a_tile_of_two_groups", "channels_of_no_group_count"])
def test_what_the_grouped_kernel_cannot_tile_is_refused_by_name(what):
    if what == "a_tile_of_two_groups":  # 4 heads of 64 in 4 groups: a lane tile's two heads would read two groups
        heads, G, match = 4, 4, "a lane tile's heads in one group"
    else:  # three groups of B and C behind 16 heads
        heads, G, match = 16, 3, "whole number of groups"
    C = heads * P + 2 * G * N
    pool = jnp.zeros((1, 3, heads, P, N), jnp.float32)
    tails = jnp.zeros((1, 3, K - 1, ss.tail_rows(C), ss.LANES), jnp.float32)
    args = (jnp.zeros((2, C)), jnp.ones((2, heads)), jnp.zeros((K, C)), jnp.zeros((C,)), -jnp.ones((heads,)), jnp.ones((heads,)), pool, tails, 0,
            jnp.arange(2, dtype=jnp.int32), jnp.ones((2,), bool), jnp.zeros((2,), bool))
    with pytest.raises(ValueError, match=match):
        ss.ssd_decode(*args, impl="pallas_interpret")
