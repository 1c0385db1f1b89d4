"""``grouped_matmul(..., transposed=True)``: the weights stored by their OUTPUT
rows, ``[G, N, K]``, multiplied as ``x w[g]^T`` (PR 59: a routed two-matrix
expert's input matrix ``w_in_t`` ``[experts, 1856, 2688]``, whose ``[2688,
1856]`` form the device keeps the other way round). Both implementations
against the untransposed call on the swapped stack, and ``routed_ffn`` with
``w_in_t`` against ``routed_ffn`` with ``w_in``; the kernel's form is forward only."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.grouped_matmul import grouped_matmul
from deepspeed_tpu.moe.routed_ffn import routed_ffn


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
@pytest.mark.parametrize(
    "m, k, n, sizes, stack, offset",
    [
        (24, 64, 40, [5, 0, 7, 3, 0, 0, 4, 1], 8, 0),  # an N of no whole lane tiles, fewer rows than a tile, empty groups
        (300, 256, 232, [100, 1, 0, 150, 20], 5, 0),  # groups across row tiles
        (128, 4096, 232, [60, 8, 60], 9, 3),  # K in two tiles; matrices 3..5 of a longer stack
        (512, 4096, 256, [9, 0, 17, 0, 0, 30, 1, 0, 25, 0, 0, 0], 20, 5),  # K in two tiles and dead visits
    ],
)
def test_the_transposed_stack_gives_what_the_plain_one_gives(impl, m, k, n, sizes, stack, offset):
    rng = np.random.default_rng(0)
    x, w = jnp.asarray(rng.standard_normal((m, k)), jnp.float32), jnp.asarray(rng.standard_normal((stack, k, n)), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    live = int(sizes.sum())
    want = grouped_matmul(x, w, sizes, group_offset=offset, impl="xla")
    got = grouped_matmul(x, jnp.swapaxes(w, 1, 2), sizes, group_offset=offset, impl=impl, transposed=True)
    assert got.shape == want.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got)[:live], np.asarray(want)[:live], atol=2e-4 * np.sqrt(k / 64), rtol=1e-5)


def test_the_transposed_kernel_is_forward_only_and_the_xla_form_differentiates():
    rng = np.random.default_rng(1)
    x, w = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32), jnp.asarray(rng.standard_normal((3, 40, 64)), jnp.float32)
    sizes = jnp.asarray([10, 0, 22], jnp.int32)
    loss = lambda impl, **kw: lambda x, w: jnp.sum(grouped_matmul(x, w, sizes, impl=impl, **kw) ** 2)
    with pytest.raises(NotImplementedError, match="no gradient through the kernel"):
        jax.grad(loss("pallas_interpret", transposed=True), argnums=(0, 1))(x, w)
    gx, gw = jax.grad(loss("xla", transposed=True), argnums=(0, 1))(x, w)
    wx, ww = jax.grad(loss("xla"), argnums=(0, 1))(x, jnp.swapaxes(w, 1, 2))
    np.testing.assert_allclose(np.asarray(gx), np.asarray(wx), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(jnp.swapaxes(ww, 1, 2)), rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("activation", ["relu2", "relu", "gelu"])
def test_routed_ffn_reads_w_in_t_as_w_in_transposed(activation):
    rng = np.random.default_rng(2)
    S, H, I, E, k = 20, 32, 24, 6, 2
    tokens, logits = jnp.asarray(rng.standard_normal((S, H)), jnp.float32), jnp.asarray(rng.standard_normal((S, E)), jnp.float32)
    w_in, w_out = jnp.asarray(rng.standard_normal((E, H, I)) * 0.3, jnp.float32), jnp.asarray(rng.standard_normal((E, I, H)) * 0.3, jnp.float32)
    kw = dict(k=k, activation=activation, norm_topk_prob=True, scoring="sigmoid", held=(2, 3))
    want, counts, _ = routed_ffn({"w_in": w_in[2:5], "w_out": w_out[2:5]}, tokens, logits, **kw)
    got, counts_t, _ = routed_ffn({"w_in_t": jnp.swapaxes(w_in, 1, 2)[2:5], "w_out": w_out[2:5]}, tokens, logits, **kw)
    assert np.array_equal(np.asarray(counts), np.asarray(counts_t)) and int(counts.sum()) > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    if activation == "relu2":  # and relu2 is the rectified input squared, not the rectified square's root
        plain, _, _ = routed_ffn({"w_in": w_in[2:5], "w_out": w_out[2:5]}, tokens, logits, **{**kw, "activation": "relu"})
        assert float(jnp.abs(plain - want).max()) > 1e-2
