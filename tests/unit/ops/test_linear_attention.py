"""The gated delta rule (``ops/transformer/linear_attention.py``): the
chunkwise form against the plain recurrence, and the one-token kernel, which
takes a row from its projections to the recurrence's output, against the
composition it replaced (``short_conv``, ``linear_qkv``, ``kda_step`` and
the shifted tail).

Tolerances: everything is float32. The chunkwise form differs from the scan
by the order of its sums and by a triangular solve: 1e-5 on outputs of size
~0.5 at ordinary decays; 1e-3 (states of size ~2.4) where the decays are so strong that the
cumulative log decay inside a chunk passes -50,000 and ``b`` sits at 2 (the
solve then sees the worst-conditioned ``I + A`` there is, and an update
with eigenvalue -1 along k does not contract the rounding before it)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import hybrid_moe as hm
from deepspeed_tpu.ops.transformer import linear_attention as la


def _inputs(B, T, H, D, seed, strong=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k, v = (jax.random.normal(ks[i], (B, T, H, D)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(D)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    log_a = -jnp.exp(jax.random.normal(ks[3], (B, T, H, D)) * (2.0 if strong else 1.0) + (2.5 if strong else -2.0))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)) * 3 + (4 if strong else 0))
    return q, k, v, log_a, beta, jax.random.normal(ks[5], (B, H, D, D)) * 0.1


@pytest.mark.parametrize("T", [1, 64, 150])
@pytest.mark.parametrize("strong", [False, True], ids=["ordinary_decay", "underflowing_decay_b_near_2"])
def test_chunked_is_the_recurrence(T, strong):
    """Whole chunks, a ragged last chunk and one token. ``strong``: per-channel
    log decays down to -38,000 a token (``exp`` of them is exactly 0 in
    float32, and the inverse cumulative decay would be ``inf``), ``b`` up to
    1.99 and beyond: a quotient of cumulative products is NaN here, the differences
    formed in log space are not."""
    q, k, v, log_a, beta, S = _inputs(2, T, 3, 32, seed=T, strong=strong)
    if strong:
        assert float(log_a.min()) < -1000 and float(beta.max()) > 1.99
        assert not np.isfinite(np.exp(-np.cumsum(np.asarray(log_a, np.float64), 1))).all() or T == 1
    o_ref, S_ref = jax.jit(la.kda_recurrent)(q, k, v, log_a, beta, S)
    o, S_new = jax.jit(la.kda_chunked)(q, k, v, log_a, beta, S)
    tol = 1e-3 if strong else 1e-5
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(S_new).all())
    assert float(jnp.abs(o - o_ref).max()) < tol
    assert float(jnp.abs(S_new - S_ref).max()) < tol


def test_dead_positions_leave_the_state_alone():
    """``log_a`` 0 and ``b`` 0: whatever q, k, v hold there."""
    q, k, v, log_a, beta, S = _inputs(2, 70, 2, 16, seed=5)
    live = jnp.arange(70) < 23
    log_a = jnp.where(live[None, :, None, None], log_a, 0.0)
    beta = jnp.where(live[None, :, None], beta, 0.0)
    _, S_masked = la.kda_chunked(q, k, v, log_a, beta, S)
    _, S_short = la.kda_chunked(q[:, :23], k[:, :23], v[:, :23], log_a[:, :23], beta[:, :23], S)
    assert float(jnp.abs(S_masked - S_short).max()) < 1e-6


def _decode_inputs(R, H, D, K, L, NS, seed, dtype=jnp.float32):
    """A layer's one-token rows as the projections leave them, the taps, and
    pools that hold something everywhere: (qkv [R, 3, H, D], log_a, beta,
    conv_w [K, 3, H, D], state pool [L, NS, H, D, D], tail pool [L, NS, K - 1, 3, H, D])."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    qkv = jax.random.normal(ks[0], (R, 3, H, D)).astype(dtype)
    log_a = -jnp.exp(jax.random.normal(ks[1], (R, H, D)) - 2.0)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[2], (R, H)))
    conv_w = (jax.random.normal(ks[3], (K, 3, H, D)) * 0.5).astype(dtype)
    pool = jax.random.normal(ks[4], (L, NS, H, D, D)) * 0.1
    tails = jax.random.normal(ks[5], (L, NS, K - 1, 3, H, D)).astype(dtype)
    return qkv, log_a, beta, conv_w, pool, tails


_decode = jax.jit(la.kda_decode, static_argnames="impl")


def _model_qkv(conv_w, tail, window):
    """The model's own ``short_conv`` and ``linear_qkv`` (``models/hybrid_moe.py``)
    of ``window`` [R, T, 3, H, D] after ``tail`` [R, K - 1, 3, H, D], with the
    taps ``conv_w`` [K, 3, H, D] as the model keeps them: q, k, v [R, T, H, D]."""
    K, _, H, D = conv_w.shape
    flat = conv_w.reshape(K, 3, H * D)
    p = {"conv_q": flat[:, 0], "conv_k": flat[:, 1], "conv_v": flat[:, 2]}
    cfg = types.SimpleNamespace(linear_num_heads=H, linear_head_dim=D)  # all that ``linear_qkv`` reads of a config
    return hm.linear_qkv(cfg, hm.short_conv(p, tail.reshape(tail.shape[:2] + (-1,)), window.reshape(window.shape[:2] + (-1,))))


@jax.jit
def _composition(qkv, log_a, beta, conv_w, S, tail):
    """What the serving step did before the kernel took it all: the model's
    ``short_conv`` and ``linear_qkv`` on (tail, token), one ``kda_step``, and
    the tail shifted by the token. ``S`` [R, H, D, D] and ``tail``
    [R, K - 1, 3, H, D] the rows' own, zeroed already where a row is fresh."""
    o, S = la.kda_step(S, *(a[:, 0] for a in _model_qkv(conv_w, tail, qkv[:, None])), log_a, beta)
    return o, S, jnp.concatenate([tail[:, 1:], qkv[:, None].astype(tail.dtype)], axis=1)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("H,K,dtype", [(32, 4, jnp.bfloat16), (64, 4, jnp.float32)], ids=["kimi_32_heads", "solar_64_heads"])
def test_decode_updates_live_rows_in_place_and_no_others(impl, H, K, dtype):
    """The fused entry against the composition it replaces, at both cells'
    head counts. Rows and slots differ; row 2 is dead (it may not touch any
    request's slot, only the spare last one, in either pool); row 1 is fresh
    (zero state AND zero tail, whatever its slot held in either). Other layers
    and other slots keep their bytes."""
    R, D, L, NS = 5, 128, 2, 7
    qkv, log_a, beta, conv_w, pool, tails = _decode_inputs(R, H, D, K, L, NS, seed=3, dtype=dtype)
    slots, live, fresh = jnp.array([3, 1, 0, 5, 2]), jnp.array([1, 1, 0, 1, 1], bool), jnp.array([0, 1, 0, 0, 0], bool)
    o, new, new_tails = _decode(qkv, log_a, beta, conv_w, pool, tails, 1, slots, live, fresh, impl=impl)
    assert new.dtype == pool.dtype and new_tails.dtype == tails.dtype and o.dtype == jnp.float32
    zeroed = lambda a: jnp.where(fresh.reshape((-1,) + (1,) * (a.ndim - 1)), 0, a)
    o_ref, S_ref, tail_ref = _composition(qkv, log_a, beta, conv_w, zeroed(pool[1, slots]), zeroed(tails[1, slots]))
    assert float(jnp.abs(o - o_ref)[live].max()) < 1e-6
    assert float(jnp.abs(new[1, slots] - S_ref)[live].max()) < 1e-6
    assert bool((new_tails[1, slots] == tail_ref)[live].all())  # a shift: no arithmetic
    for got, was in ((new, pool), (new_tails, tails)):
        assert bool((got[0] == was[0]).all())  # another layer
        for untouched in (0, 4):  # the dead row's slot, a slot no row names
            assert bool((got[1, untouched] == was[1, untouched]).all())


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_two_decode_steps_of_a_row_are_the_chunked_form_on_both(impl):
    """A fresh row's first two tokens, one a step through ``kda_decode``,
    against ``short_conv`` + ``kda_chunked`` on the two together: the same
    outputs, the same state, and the tail the window would have left."""
    R, H, D, K, NS = 3, 16, 128, 4, 4
    first, log_a1, beta1, conv_w, pool, tails = _decode_inputs(R, H, D, K, 1, NS, seed=11)
    second, log_a2, beta2, *_ = _decode_inputs(R, H, D, K, 1, NS, seed=12)
    slots, live = jnp.array([2, 0, 1]), jnp.ones(R, bool)
    o1, pool, tails = _decode(first, log_a1, beta1, conv_w, pool, tails, 0, slots, live, jnp.ones(R, bool), impl=impl)
    o2, pool, tails = _decode(second, log_a2, beta2, conv_w, pool, tails, 0, slots, live, jnp.zeros(R, bool), impl=impl)
    qkv = _model_qkv(conv_w, jnp.zeros((R, K - 1, 3, H, D)), jnp.stack([first, second], axis=1))
    o_c, S_c = la.kda_chunked(*qkv, jnp.stack([log_a1, log_a2], 1), jnp.stack([beta1, beta2], 1), jnp.zeros((R, H, D, D)))
    assert float(jnp.abs(o_c - jnp.stack([o1, o2], 1)).max()) < 1e-5
    assert float(jnp.abs(S_c - pool[0, slots]).max()) < 1e-5
    want_tail = jnp.concatenate([jnp.zeros((R, K - 3, 3, H, D)), first[:, None], second[:, None]], axis=1)
    assert bool((tails[0, slots] == want_tail).all())


def test_decode_is_one_step_of_the_chunked_form():
    """A decode row riding in a wide window and the narrow program's row
    leave the same state (heads of 16 channels: the XLA form takes any)."""
    R, H, D, K = 3, 8, 16, 4
    qkv, log_a, beta, conv_w, pool, tails = _decode_inputs(R, H, D, K, 1, R + 1, seed=8)
    slots = jnp.arange(R)
    o_d, new, _ = _decode(qkv, log_a, beta, conv_w, pool, tails, 0, slots, jnp.ones(R, bool), jnp.zeros(R, bool), impl="xla")
    q, k, v = la.decode_qkv(conv_w, [tails[0, slots, j] for j in range(K - 1)] + [qkv])
    o_c, S_c = la.kda_chunked(q[:, None], k[:, None], v[:, None], log_a[:, None], beta[:, None], pool[0, slots])
    assert float(jnp.abs(o_c[:, 0] - o_d).max()) < 1e-6 and float(jnp.abs(S_c - new[0, :R]).max()) < 1e-6


def test_unknown_impl_and_ragged_head_count_are_refused():
    qkv, log_a, beta, conv_w, pool, tails = _decode_inputs(1, 3, 16, 4, 1, 2, seed=0)
    rows = (0, jnp.zeros(1, jnp.int32), jnp.ones(1, bool), jnp.zeros(1, bool))
    with pytest.raises(ValueError, match="multiple of 16 heads"):
        la.kda_decode(qkv, log_a, beta, conv_w, pool, tails, *rows, impl="pallas_interpret")
    with pytest.raises(ValueError, match="unknown kda_decode impl"):
        la.kda_decode(qkv, log_a, beta, conv_w, pool, tails, *rows, impl="cuda")
