"""The gated delta rule (``ops/transformer/linear_attention.py``): the
chunkwise form and the one-token kernel against the plain recurrence.

Tolerances: everything is float32. The chunkwise form differs from the scan
by the order of its sums and by a triangular solve: 1e-5 on outputs of size
~0.5 at ordinary decays; 1e-3 (states of size ~2.4) where the decays are so strong that the
cumulative log decay inside a chunk passes -50,000 and ``b`` sits at 2 (the
solve then sees the worst-conditioned ``I + A`` there is, and an update
with eigenvalue -1 along k does not contract the rounding before it)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_decode_updates_live_rows_in_place_and_no_others(impl):
    """Rows and slots differ; row 2 is dead (it may not touch any request's
    slot, only the spare last one); row 1 is fresh (zero state, whatever its
    slot held). Other layers and other slots keep their bytes."""
    R, H, D = 5, 8, 128
    q, k, v, log_a, beta, _ = _inputs(R, 1, H, D, seed=3)
    q, k, v, log_a, beta = (a[:, 0] for a in (q, k, v, log_a, beta))
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 7, H, D, D))
    slots, live, fresh = jnp.array([3, 1, 0, 5, 2]), jnp.array([1, 1, 0, 1, 1], bool), jnp.array([0, 1, 0, 0, 0], bool)
    o, new = la.kda_decode(q, k, v, log_a, beta, pool, 1, slots, live, fresh, impl=impl)
    o_ref, S_ref = la.kda_step(jnp.where(fresh[:, None, None, None], 0.0, pool[1, slots]), q, k, v, log_a, beta)
    assert float(jnp.abs(o - o_ref)[live].max()) < 1e-6
    assert float(jnp.abs(new[1, slots] - S_ref)[live].max()) < 1e-6
    assert bool((new[0] == pool[0]).all())  # another layer
    for untouched in (0, 4):  # the dead row's slot, a slot no row names
        assert bool((new[1, untouched] == pool[1, untouched]).all())


def test_decode_is_one_step_of_the_chunked_form():
    """A decode row riding in a wide window and the narrow program's row
    leave the same state."""
    R, H, D = 3, 8, 16
    q, k, v, log_a, beta, S = _inputs(R, 1, H, D, seed=8)
    o_c, S_c = la.kda_chunked(q, k, v, log_a, beta, S)
    pool = jnp.zeros((1, R + 1, H, D, D)).at[0, :R].set(S)
    o_d, new = la.kda_decode(q[:, 0], k[:, 0], v[:, 0], log_a[:, 0], beta[:, 0], pool, 0, jnp.arange(R), jnp.ones(R, bool), jnp.zeros(R, bool), impl="xla")
    assert float(jnp.abs(o_c[:, 0] - o_d).max()) < 1e-6 and float(jnp.abs(S_c - new[0, :R]).max()) < 1e-6


def test_unknown_impl_and_ragged_head_count_are_refused():
    q = jnp.zeros((1, 3, 16))
    pool = jnp.zeros((1, 2, 3, 16, 16))
    with pytest.raises(ValueError, match="multiple of 8 heads"):
        la.kda_decode(q, q, q, q, q[..., 0], pool, 0, jnp.zeros(1, jnp.int32), jnp.ones(1, bool), jnp.zeros(1, bool), impl="pallas_interpret")
    with pytest.raises(ValueError, match="unknown kda_decode impl"):
        la.kda_decode(q, q, q, q, q[..., 0], pool, 0, jnp.zeros(1, jnp.int32), jnp.ones(1, bool), jnp.zeros(1, bool), impl="cuda")
