"""The dropless routed FFN (``moe/routed_ffn.py``) and its grouped matmul
(``moe/grouped_matmul.py``): the sorted path against the capacity-einsum path
at ``capacity = S``, the kernel (interpret mode) against ``ragged_dot``,
dead tokens, gradients, int8 experts, and what must raise."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.compression.int8 import quantize_params_int8
from deepspeed_tpu.moe import MoE, sharded_moe
from deepspeed_tpu.moe.experts import apply_expert_ffn, init_expert_ffn
from deepspeed_tpu.moe.grouped_matmul import grouped_matmul
from deepspeed_tpu.moe import routed_ffn as routed_ffn_module
from deepspeed_tpu.moe.routed_ffn import route
from deepspeed_tpu.parallel import mesh as mesh_mod
from deepspeed_tpu.runtime.config import MeshConfig

S, E, H, I = 48, 8, 32, 24


def routed_ffn(experts, tokens, logits, live=None, **static):
    """One compiled program a call (op by op, each case compiles dozens)."""
    return jax.jit(lambda e, t, lg, lv: routed_ffn_module.routed_ffn(e, t, lg, live=lv, **static))(experts, tokens, logits, live)


def layer_inputs(seed=0, activation="swiglu", use_bias=False):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    experts = init_expert_ffn(k1, E, H, I, activation=activation, use_bias=use_bias, std=0.3)
    if use_bias:
        experts = {k: v + 0.1 * jax.random.normal(jax.random.fold_in(k1, i), v.shape) for i, (k, v) in enumerate(sorted(experts.items()))}
    return experts, jax.random.normal(k2, (S, H)), 2.0 * jax.random.normal(k3, (S, E))


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("activation, use_bias", [("swiglu", False), ("gelu", True)])
def test_sorted_path_equals_the_capacity_path_without_drops(k, activation, use_bias):
    """``topkgating(drop_tokens=False)`` (capacity = S, an [S, E, S] mask) with
    ``dispatch`` / ``combine`` against the sorted path: the same routing (top-1
    keeps the plain gate, top-2 renormalises), so the same outputs within
    float32 rounding, and the same counts."""
    experts, tokens, logits = layer_inputs(1, activation, use_bias)
    _, combine_w, dispatch_m, counts = sharded_moe.topkgating(logits, k, 1.0, 4, drop_tokens=False, use_rts=False)
    assert combine_w.shape == (S, E, S)
    want = sharded_moe.combine(apply_expert_ffn(experts, sharded_moe.dispatch(tokens, dispatch_m), activation), combine_w)
    got, got_counts, _ = routed_ffn(experts, tokens, logits, k=k, activation=activation, norm_topk_prob=k > 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_counts), np.asarray(counts))


@pytest.mark.parametrize("k, norm", [(1, False), (3, False), (3, True), (8, False)])
def test_routed_ffn_is_the_weighted_sum_over_the_chosen_experts(k, norm):
    experts, tokens, logits = layer_inputs(2)
    got, counts, gates = routed_ffn(experts, tokens, logits, k=k, activation="swiglu", norm_topk_prob=norm)
    every = apply_expert_ffn(experts, jnp.broadcast_to(tokens, (E, S, H)), "swiglu")  # [E, S, H]: each expert on every token
    top, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if norm:
        top = top / top.sum(-1, keepdims=True)
    want = sum(top[:, j, None] * every[chosen[:, j], jnp.arange(S)] for j in range(k))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    assert int(counts.sum()) == S * k and counts.shape == (E,)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)


def test_dead_tokens_are_not_routed():
    experts, tokens, logits = layer_inputs(3)
    live = jnp.arange(S) % 3 != 0
    got, counts, _ = routed_ffn(experts, tokens, logits, k=3, activation="swiglu", norm_topk_prob=False, live=live)
    all_live, _, _ = routed_ffn(experts, tokens, logits, k=3, activation="swiglu", norm_topk_prob=False)
    assert int(counts.sum()) == int(live.sum()) * 3
    np.testing.assert_array_equal(np.asarray(got)[~np.asarray(live)], 0.0)
    np.testing.assert_allclose(np.asarray(got)[np.asarray(live)], np.asarray(all_live)[np.asarray(live)], atol=1e-6)
    # what a dead token holds cannot reach a live one (not even a NaN)
    poisoned, _, _ = routed_ffn(experts, jnp.where(live[:, None], tokens, jnp.nan), logits, k=3, activation="swiglu", norm_topk_prob=False, live=live)
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(got))
    none_live, counts0, _ = routed_ffn(experts, tokens, logits, k=3, activation="swiglu", norm_topk_prob=False, live=jnp.zeros(S, bool))
    assert int(counts0.sum()) == 0 and not np.asarray(none_live).any()


def by_hand(x, w, sizes, offset=0):
    out, start = np.zeros((x.shape[0], w.shape[2]), np.float32), 0
    for g, n in enumerate(sizes):
        out[start : start + n] = x[start : start + n] @ w[offset + g]
        start += n
    return out, start


@pytest.mark.parametrize("impl", ["pallas_interpret", "xla"])
@pytest.mark.parametrize(
    "m, k, n, sizes, stack, offset",
    [
        (24, 64, 32, [5, 0, 7, 3, 0, 0, 4, 1], 8, 0),  # fewer rows than a tile, empty groups, 4 rows of no group
        (300, 256, 384, [100, 1, 0, 150, 20], 5, 0),  # groups across row tiles; 300 pads to 384
        (256, 128, 128, [0, 256, 0, 0], 4, 0),  # one group owns everything
        (256, 128, 128, [0, 0, 0, 0], 4, 0),  # no assignment at all
        (128, 4096, 256, [60, 8, 60], 9, 3),  # K in two tiles; the groups are matrices 3..5 of a longer stack
        # K in two tiles AND dead visits (where a dead visit re-read an expert's matrix until PR 37): every row in
        # tile 0 of four, empty groups between and behind the live ones, matrices 5..16 of a longer stack
        (512, 4096, 256, [9, 0, 17, 0, 0, 30, 1, 0, 25, 0, 0, 0], 20, 5),
        # the same over several row tiles: groups that cross a tile's edge, three row tiles of eight without a row
        (1024, 4096, 384, [100, 0, 60, 200, 0, 0, 150, 3, 0, 0], 14, 4),
    ],
)
def test_grouped_matmul_against_a_loop(impl, m, k, n, sizes, stack, offset):
    rng = np.random.default_rng(0)
    x, w = rng.standard_normal((m, k)).astype(np.float32), rng.standard_normal((stack, k, n)).astype(np.float32)
    want, live = by_hand(x, w, sizes, offset)
    got = np.asarray(grouped_matmul(jnp.asarray(x), jnp.asarray(w), jnp.asarray(sizes, jnp.int32), group_offset=offset, impl=impl))
    np.testing.assert_allclose(got[:live], want[:live], atol=2e-4 * np.sqrt(k / 64), rtol=1e-5)  # rows past the groups are undefined


def test_grouped_matmul_shapes_bench_rehearses():
    """``tools/grouped_matmul_shapes_bench.py --rehearse``: the tool's control flow, tiny, on the CPU: a line a window."""
    import json
    import pathlib
    import subprocess
    import sys

    tool = pathlib.Path(__file__).parents[3] / "tools" / "grouped_matmul_shapes_bench.py"
    done = subprocess.run([sys.executable, str(tool), "--rehearse"], capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]
    assert [(line["shape"], line["window"], line["device"]) for line in lines] == [("tiny", "narrow", "cpu"), ("tiny", "mixed", "cpu")]
    for line in lines:
        assert 0 < line["experts_hit"] <= line["live"] < line["rows"] and line["least_us_a_layer"] > 0
        assert all(line[impl][key] > 0 for impl in ("pallas", "xla") for key in ("up_us", "down_us", "layer_us", "layer_roofline_pct"))


def test_the_kernels_gradient_is_ragged_dots():
    rng = np.random.default_rng(4)
    x, w = jnp.asarray(rng.standard_normal((48, 64)), jnp.float32), jnp.asarray(rng.standard_normal((6, 64, 32)), jnp.float32)
    sizes = jnp.asarray([10, 0, 17, 13], jnp.int32)  # 8 rows of no group; the groups are matrices 1..4 of the stack

    def loss(x_, w_, impl):
        out = grouped_matmul(x_, w_, sizes, group_offset=1, impl=impl)
        return jnp.sum(out[:40] ** 2)

    got = jax.grad(loss, argnums=(0, 1))(x, w, "pallas_interpret")
    want = jax.grad(loss, argnums=(0, 1))(x, w, "xla")
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all() and np.abs(np.asarray(b)).max() > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


def test_int8_experts_ride_the_sorted_path():
    experts, tokens, logits = layer_inputs(5)
    exact, _, _ = routed_ffn(experts, tokens, logits, k=3, activation="swiglu", norm_topk_prob=False)
    quantized = quantize_params_int8({"moe": {"experts": experts}})["moe"]["experts"]
    got, _, _ = routed_ffn(quantized, tokens, logits, k=3, activation="swiglu", norm_topk_prob=False)
    assert float(jnp.abs(got - exact).max()) < 0.05 * float(jnp.abs(exact).max())  # the int8 roundtrip, not a wrong scale


def test_route_takes_any_k_up_to_the_experts():
    logits = jax.random.normal(jax.random.PRNGKey(6), (S, E))
    for k in (1, 5, E):
        gates, chosen, weights = route(logits, k, norm_topk_prob=False)
        assert chosen.shape == weights.shape == (S, k)
        np.testing.assert_allclose(np.asarray(weights), np.asarray(jnp.take_along_axis(gates, chosen, -1)))
    np.testing.assert_allclose(np.asarray(route(logits, E, False)[2].sum(-1)), 1.0, rtol=1e-5)
    with pytest.raises(ValueError, match="k=9"):
        route(logits, E + 1, False)


class TestMoELayer:
    def teardown_method(self):
        mesh_mod.reset_topology()

    def test_dropless_layer_takes_the_sorted_path_for_any_k(self):
        mesh_mod.reset_topology()
        layer = MoE(H, num_experts=E, k=3, drop_tokens=False, intermediate_size=I, activation="swiglu", use_bias=False, norm_topk_prob=False)
        params = layer.init(jax.random.PRNGKey(7))
        x = jax.random.normal(jax.random.PRNGKey(8), (4, 12, H))
        out, l_aux, counts = layer.apply(params, x, train=True, rng=jax.random.PRNGKey(9))
        assert out.shape == x.shape and int(counts.sum()) == 48 * 3 and float(l_aux) > 0
        # uniform gates: E * sum_e (1/E) * share_e = 1 whatever the split, as top1gating's balanced case
        _, l_even, _ = layer.apply({**params, "gate": {"wg": jnp.zeros((H, E))}}, x, train=False)
        assert float(l_even) == pytest.approx(1.0, rel=1e-5)
        grads = jax.grad(lambda p: jnp.sum(layer.apply(p, x, train=False)[0] ** 2))(params)
        assert float(jnp.abs(grads["gate"]["wg"]).max()) > 0  # the router learns through the gates

    def test_capacity_routing_still_refuses_k_above_two(self):
        layer = MoE(H, num_experts=E, k=3, drop_tokens=True, intermediate_size=I)
        with pytest.raises(ValueError, match="drop_tokens"):
            layer.apply(layer.init(jax.random.PRNGKey(0)), jnp.zeros((2, 8, H)), train=False)

    def test_dropless_on_an_expert_axis_raises_and_does_not_fall_back(self, eight_devices):
        mesh_mod.initialize_topology(MeshConfig(data=4, expert=2))
        layer = MoE(H, num_experts=E, k=2, drop_tokens=False, intermediate_size=I)
        with pytest.raises(NotImplementedError, match="expert-parallel"):
            layer.apply(layer.init(jax.random.PRNGKey(0)), jnp.zeros((2, 8, H)), train=False)
