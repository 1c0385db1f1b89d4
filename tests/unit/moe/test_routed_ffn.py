"""The dropless routed FFN (``moe/routed_ffn.py``) and its grouped matmul
(``moe/grouped_matmul.py``): the sorted path against the capacity-einsum path
at ``capacity = S``, the kernel (interpret mode) against ``ragged_dot``,
dead tokens, gradients, int8 experts, and what must raise."""

import re

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


# --- the assignment plan (``moe/route_plan.py``) ---------------------------------

# (S, E, k, held, live, scoring, bias, ties): the eight routed cells' narrow steps, the unit tests' own width, and slabs
# of two token blocks (the second with a last block of 8 tokens)
_PLAN_CASES = {
    "lfm2_glm": (64, 64, 4, (0, 8), True, "sigmoid", True, False),
    "laguna": (64, 256, 10, (16, 16), True, "softmax", False, False),
    "mimo": (64, 256, 8, (240, 16), True, "sigmoid", True, False),
    "solar": (64, 320, 8, (40, 40), True, "sigmoid", True, False),
    "kimi": (64, 256, 8, (32, 32), False, "sigmoid", True, False),
    "nemotron": (64, 128, 6, (64, 64), True, "sigmoid", True, False),
    "olmoe": (16, 64, 8, None, True, "softmax", False, False),
    "unit": (48, 8, 3, None, False, "softmax", False, False),
    "unit_every_expert": (48, 8, 8, None, True, "softmax", False, False),
    "tied_scores": (64, 64, 8, (8, 24), True, "softmax", False, True),
    "tied_biased_scores": (16, 8, 3, None, False, "sigmoid", True, True),
    "olmoe_token_tile": (1024, 64, 8, None, True, "softmax", False, False),
    "ragged_last_block": (520, 16, 3, (4, 8), True, "sigmoid", True, True),
}


def _numpy_plan(logits, k, scoring, select_bias, live, held):
    """The plan by numpy's stable sorts, on the float32 scores ``route`` takes
    its choice from: chosen [S, k] (the largest first, the lowest index among
    equals), dest [S, k], routed [S, k], counts [n], row_expert [S k], src [S k]."""
    from deepspeed_tpu.moe.route_plan import scores

    select = np.asarray(scores(logits, scoring))
    if select_bias is not None:
        select = select + np.asarray(select_bias, np.float32)
    chosen = np.argsort(-select, axis=1, kind="stable")[:, :k]
    n, buckets = logits.shape[1], chosen
    if held is not None:
        first, n = held
        buckets = np.where((chosen >= first) & (chosen < first + n), chosen - first, n)
    if live is not None:
        buckets = np.where(np.asarray(live)[:, None], buckets, n)
    flat = buckets.reshape(-1)
    order = np.argsort(flat, kind="stable")
    dest = np.empty_like(order)
    dest[order] = np.arange(order.size)
    return chosen, dest.reshape(buckets.shape), buckets < n, np.bincount(flat, minlength=n + 1)[:n], np.minimum(flat[order], n - 1), order // k


@pytest.mark.parametrize("impl", ["sorted", "pallas_interpret"])
@pytest.mark.parametrize("case", _PLAN_CASES)
def test_plan_equals_the_sorted_forms(case, impl):
    """The chosen experts, each assignment's row, the counts, each sorted
    row's expert and token, of the kernel and of the form that runs where it
    does not: integer for integer what numpy's stable sorts give (tied
    scores: the lowest index first, as ``lax.top_k``), and the kernel's
    weights the sorted form's to float32 rounding."""
    from deepspeed_tpu.moe.route_plan import route_plan

    rows, width, k, held, with_live, scoring, with_bias, ties = _PLAN_CASES[case]
    rng = np.random.default_rng(len(case))
    logits = 2.0 * rng.standard_normal((rows, width))
    if ties:
        logits = np.round(logits)  # whole numbers: many a token's equal scores
    logits = jnp.asarray(logits, jnp.float32)
    static = dict(
        k=k, norm_topk_prob=True, scoring=scoring, held=held,
        select_bias=jnp.asarray(np.round(rng.standard_normal(width)) if ties else 0.1 * rng.standard_normal(width), jnp.float32) if with_bias else None,
        live=jnp.asarray(rng.random(rows) < 0.7) if with_live else None,
    )
    got = route_plan(logits, impl=impl, **static)
    chosen, dest, routed, counts, row_expert, src = _numpy_plan(logits, k, scoring, static["select_bias"], static["live"], held)
    for name, want in (("chosen", chosen.T), ("dest", dest.T), ("routed", routed.T), ("counts", counts), ("row_expert", row_expert), ("src", src)):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)), want, err_msg=name)
    np.testing.assert_allclose(np.asarray(got.weights), np.asarray(route_plan(logits, impl="sorted", **static).weights), rtol=2e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(got.weights).sum(0), 1.0, rtol=1e-5)  # ``norm_topk_prob``
    # a group's sorted row holds its assignment's gate: ``row_weight`` against ``weights`` through ``dest``, bit for bit
    np.testing.assert_array_equal(np.asarray(got.row_weight)[dest.T[routed.T]], np.asarray(got.weights)[routed.T])
    assert sorted(np.asarray(got.dest).reshape(-1)) == list(range(rows * k))  # a permutation: every row has one assignment


@pytest.mark.parametrize("noise", ["RSample", None], ids=["noise_picks", "gates_pick"])
def test_dropless_layers_gradient_is_the_sorted_forms(noise, monkeypatch):
    """``jax.grad`` through ``MoE.apply`` (dropless; training's noise picking
    the experts, or the gates themselves) with the kernel's plan is the
    gradient with the sorted form's, the parent's: the plan's integers carry
    nothing, the weights' gradient is the ``jnp`` formulas' at the chosen
    experts."""
    mesh_mod.reset_topology()
    layer = MoE(H, num_experts=E, k=3, drop_tokens=False, intermediate_size=I, activation="swiglu", use_bias=False, noisy_gate_policy=noise)
    params = layer.init(jax.random.PRNGKey(10))
    x = jax.random.normal(jax.random.PRNGKey(11), (4, 12, H))

    def grads(form):
        monkeypatch.setattr(routed_ffn_module, "route_plan", lambda *a, impl="auto", **kw: plan(*a, impl=form, **kw))

        def loss(p):
            out, l_aux, _ = layer.apply(p, x, train=True, rng=jax.random.PRNGKey(12))
            return jnp.sum(out**2) + 0.1 * l_aux

        return jax.jit(jax.grad(loss))(params)

    plan = routed_ffn_module.route_plan
    got, want = grads("pallas_interpret"), grads("sorted")
    assert float(jnp.abs(want["gate"]["wg"]).max()) > 0
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5 * float(jnp.abs(b).max()), rtol=1e-5)
    mesh_mod.reset_topology()


@pytest.mark.parametrize(
    "on_a_tpu, tokens, want",
    [(True, 64, ("kernel", 1)), (True, 1024, ("kernel", 2)), (True, 520, ("kernel", 2)), (True, 8, ("kernel", 1)),
     (True, 2048, ("sorted", 0)), (True, 60, ("sorted", 0)), (False, 64, ("sorted", 0))],
)
def test_the_plans_path_follows_the_shape_and_the_backend(on_a_tpu, tokens, want, monkeypatch):
    """``plan_path``: what ``auto`` takes, and what an engine records as ``moe.route_plan``."""
    from deepspeed_tpu.moe import route_plan

    monkeypatch.setattr(route_plan, "on_tpu", lambda: on_a_tpu)
    combine = {"kernel": "live_rows", "sorted": "gather"}[want[0]]  # the two ways go with the plan: one observable
    assert route_plan.plan_path(tokens, 64, 4) == {"path": want[0], "S": tokens, "E": 64, "k": 4, "blocks": want[1], "combine": combine}


# --- the two ways between token order and expert order (``moe/live_rows.py``) ----------

# (S, E, k, held, live tokens (None: no ``live``), H, the tokens' type): the eight routed cells' narrow steps and token
# tiles (a mixed step's tile: its first ~190 tokens live, OLMoE's 143 of 1,024), then the edges: no row at all, every row
# (no ``held``, no ``live``), a tile whose last tokens are dead, blocks that end inside a group, odd sizes
_WAYS_CASES = {
    "lfm2_glm_narrow": (64, 64, 4, (0, 8), 64, 128, jnp.bfloat16),
    "laguna_narrow": (64, 256, 10, (16, 16), 64, 128, jnp.bfloat16),
    "mimo_narrow": (64, 256, 8, (240, 16), 64, 128, jnp.bfloat16),
    "solar_narrow": (64, 320, 8, (40, 40), 64, 128, jnp.bfloat16),
    "kimi_narrow": (64, 256, 8, (32, 32), 64, 128, jnp.bfloat16),
    "nemotron_narrow": (64, 128, 6, (64, 64), 64, 128, jnp.bfloat16),
    "olmoe_narrow": (16, 64, 8, None, 16, 128, jnp.bfloat16),
    "lfm2_glm_tile": (512, 64, 4, (0, 8), 190, 128, jnp.bfloat16),
    "laguna_tile": (512, 256, 10, (16, 16), 190, 128, jnp.bfloat16),
    "mimo_tile": (512, 256, 8, (240, 16), 190, 128, jnp.bfloat16),
    "solar_tile": (512, 320, 8, (40, 40), 190, 128, jnp.bfloat16),
    "kimi_tile": (512, 256, 8, (32, 32), 190, 128, jnp.bfloat16),
    "nemotron_tile": (512, 128, 6, (64, 64), 190, 128, jnp.bfloat16),
    "olmoe_tile": (1024, 64, 8, None, 143, 128, jnp.bfloat16),
    "no_row": (64, 64, 4, (0, 8), 0, 128, jnp.bfloat16),
    "every_row": (48, 8, 3, None, None, 32, jnp.float32),
    "every_row_of_whole_blocks": (64, 8, 4, None, None, 128, jnp.bfloat16),
    "dead_last_tokens_float32": (72, 16, 3, (4, 8), 50, 384, jnp.float32),
    "eight_tokens": (8, 64, 8, None, 5, 128, jnp.bfloat16),
    "ragged_last_block": (520, 16, 3, (4, 8), 300, 128, jnp.bfloat16),
}


def _ways_inputs(case):
    """(tokens [S, H], the plan, the experts' outputs' stand-in [S k, H] float32 with NaN behind the groups). The plan is
    numpy's (``_numpy_plan``, what both forms of ``route_plan`` are held to above): nothing to compile a shape."""
    from deepspeed_tpu.moe.route_plan import RoutePlan

    rows, width, k, held, live_tokens, hidden, dtype = _WAYS_CASES[case]
    rng = np.random.default_rng(len(case))
    logits = jnp.asarray(2.0 * rng.standard_normal((rows, width)), jnp.float32)
    live = None if live_tokens is None else np.arange(rows) < live_tokens
    chosen, dest, routed, counts, row_expert, src = _numpy_plan(logits, k, "softmax", None, live, held)
    weights = rng.random((rows, k)).astype(np.float32)
    weights /= weights.sum(1, keepdims=True)
    row_weight = np.zeros(rows * k, np.float32)
    row_weight[dest.reshape(-1)] = weights.reshape(-1)
    plan = RoutePlan(*(jnp.asarray(x) for x in (weights.T, chosen.T.astype(np.int32), dest.T.astype(np.int32), routed.T.astype(np.int32),
                                                counts.astype(np.int32), row_expert.astype(np.int32), src.astype(np.int32), row_weight)))
    tokens = jnp.asarray(rng.standard_normal((rows, hidden)), dtype)
    out_rows = rng.standard_normal((rows * k, hidden)).astype(np.float32)
    out_rows[int(counts.sum()) :] = np.nan
    return tokens, plan, jnp.asarray(out_rows)


@pytest.mark.parametrize("case", _WAYS_CASES)
def test_live_rows_are_the_gathers(case):
    """``moe_dispatch_rows`` and ``moe_combine_rows`` in Pallas's interpreter
    against the two gathers and the sum they stand for: the rows of the
    groups bit for bit (behind them lies anything: here NaN, which must reach
    no token), the output in float32 to float32 rounding (a token's rows are
    added in expert order, not in choice order) and in the tokens' type to
    one step of it."""
    from deepspeed_tpu.moe import live_rows

    tokens, plan, out_rows = _ways_inputs(case)
    rows, _, k, held, live_tokens, _, dtype = _WAYS_CASES[case]
    total = int(jnp.sum(plan.counts))
    assert total == (rows * k if held is None and live_tokens is None else total) and (total == 0) == (live_tokens == 0)
    masked = held is not None or live_tokens is not None

    typed = case in ("laguna_narrow", "olmoe_narrow", "no_row")  # the output in the tokens' type too: another call, so only here

    def both(form, t, o, p):  # op by op: a call is its own compiled program, built once a shape
        return (live_rows.dispatch(t, p, impl=form), live_rows.combine(o, p, jnp.float32, masked=masked, impl=form),
                live_rows.combine(o, p, dtype, masked=masked, impl=form) if typed else None)

    got_rows, got, got_typed = both("pallas_interpret", tokens, out_rows, plan)
    want_rows, want, want_typed = both("gather", tokens, jnp.nan_to_num(out_rows), plan)
    assert got_rows.shape == want_rows.shape == (rows * k, tokens.shape[1]) and got_rows.dtype == want_rows.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got_rows[:total], np.float32), np.asarray(want_rows[:total], np.float32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=1e-6)
    if typed:
        step = float(jnp.finfo(dtype).eps)
        assert got_typed.dtype == want_typed.dtype == dtype
        np.testing.assert_allclose(np.asarray(got_typed, np.float32), np.asarray(want_typed, np.float32), rtol=step, atol=step)
    if total == 0:
        assert not np.asarray(got).any()


def test_live_rows_walk_column_slabs(monkeypatch):
    """A float32 accumulator of ``ACC_BYTES`` a grid step: at 512 x 4,096 the
    columns go in four slabs; here 64 x 512 in four of 128, the same walk."""
    from deepspeed_tpu.moe import live_rows

    monkeypatch.setattr(live_rows, "ACC_BYTES", 64 * 128 * 4)
    tokens, plan, out_rows = _ways_inputs("laguna_narrow")
    tokens, out_rows = jnp.tile(tokens, (1, 4)), jnp.tile(out_rows, (1, 4))
    spec = live_rows._Spec(64, 512, 640, 16, "bfloat16", True)
    assert live_rows._geometry(spec) == (640, 128)
    assert live_rows._geometry(live_rows._Spec(24, 96, 72, 16, "float32", True)) == (128, 96)  # an odd width is one slab
    total = int(jnp.sum(plan.counts))
    got = live_rows.dispatch(tokens, plan, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(got[:total], np.float32), np.asarray(live_rows.dispatch(tokens, plan, impl="gather")[:total], np.float32))
    np.testing.assert_allclose(
        np.asarray(live_rows.combine(out_rows, plan, jnp.float32, masked=True, impl="pallas_interpret")),
        np.asarray(live_rows.combine(jnp.nan_to_num(out_rows), plan, jnp.float32, masked=True, impl="gather")), rtol=2e-6, atol=1e-6,
    )
    monkeypatch.undo()
    live_rows._dispatch_call.cache_clear(), live_rows._combine_call.cache_clear()  # built under the small budget


def test_what_is_not_finite_stays_its_tokens():
    """The rows move through the MXU as products with ones and zeros, and a
    zero times an infinity is no zero: what is not finite is taken out before
    the product and comes back as NaN in its own rows and its own token, as
    a gather would leave it there and nowhere else."""
    from deepspeed_tpu.moe import live_rows

    tokens, plan, out_rows = _ways_inputs("every_row_of_whole_blocks")
    total = int(jnp.sum(plan.counts))
    src = np.asarray(plan.src)
    poisoned = tokens.at[5, 7].set(jnp.inf).at[9, 0].set(jnp.nan)
    got = np.asarray(live_rows.dispatch(poisoned, plan, impl="pallas_interpret"), np.float32)
    clean = np.asarray(live_rows.dispatch(tokens, plan, impl="gather"), np.float32)
    touched = np.isin(src, (5, 9))
    np.testing.assert_array_equal(got[~touched], clean[~touched])
    assert not np.isfinite(got[touched]).all(axis=1).any()
    bad_row = int(np.flatnonzero(src == 20)[0])
    back = np.asarray(live_rows.combine(out_rows.at[bad_row, 3].set(-jnp.inf), plan, jnp.float32, masked=False, impl="pallas_interpret"))
    want = np.asarray(live_rows.combine(jnp.nan_to_num(out_rows), plan, jnp.float32, masked=False, impl="gather"))
    assert total == src.size and not np.isfinite(back[20]).all()
    np.testing.assert_allclose(np.delete(back, 20, axis=0), np.delete(want, 20, axis=0), rtol=2e-6, atol=1e-6)
    with pytest.raises(ValueError, match="impl must be"):
        live_rows.dispatch(tokens, plan, impl="auto")


def _through(form, monkeypatch):
    """``routed_ffn`` with the plan and the two ways in ``form`` (``pallas_interpret``: what a TPU takes, interpreted)."""
    path = {"path": form, "combine": form} if form == "pallas_interpret" else {"path": "sorted", "combine": "gather"}
    monkeypatch.setattr(routed_ffn_module, "plan_path", lambda *shape: path)


@pytest.mark.parametrize(
    "activation, use_bias, k, held, live_tokens",
    [("swiglu", False, 3, (2, 4), 30), ("gelu", True, 2, None, None), ("gelu", True, 3, (0, 5), 40)],
    ids=["held_share", "biased_every_row", "biased_held_dead_tail"],
)
def test_routed_ffn_through_the_live_rows_is_the_gathers(monkeypatch, activation, use_bias, k, held, live_tokens):
    """The whole layer as a TPU runs it (the plan's kernel, ``moe_dispatch_rows``,
    the experts, ``b_in`` / ``b_out`` by the rows' experts, ``moe_combine_rows``),
    interpreted, against the sorted plan and the gathers: the output to
    float32 rounding, ``counts`` and the gates equal."""
    experts, tokens, logits = layer_inputs(6, activation, use_bias)
    live = None if live_tokens is None else jnp.arange(S) < live_tokens
    static = dict(k=k, activation=activation, norm_topk_prob=k > 1, held=held)
    if held is not None:
        experts = {name: leaf[held[0] : held[0] + held[1]] for name, leaf in experts.items()}
    _through("pallas_interpret", monkeypatch)
    got, got_counts, got_gates = routed_ffn(experts, tokens, logits, live, **static)
    _through("sorted", monkeypatch)
    want, want_counts, want_gates = routed_ffn(experts, tokens, logits, live, **static)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_counts), np.asarray(want_counts))
    np.testing.assert_array_equal(np.asarray(got_gates), np.asarray(want_gates))
    assert held is not None or int(got_counts.sum()) == k * S


@pytest.mark.parametrize("held, live_tokens, use_bias", [((2, 4), 30, False), (None, None, True)], ids=["held_share_dead_tail", "biased_every_row"])
def test_routed_ffns_gradient_through_the_live_rows_is_the_gathers(monkeypatch, held, live_tokens, use_bias):
    """``jax.grad`` of the layer w.r.t. the tokens, the router's logits and
    every expert stack through the two calls' ``custom_vjp``s (and the
    plan's, which hands ``row_weight``'s cotangent to the gates) against the
    gradient through the gathers."""
    activation = "gelu" if use_bias else "swiglu"
    experts, tokens, logits = layer_inputs(7, activation, use_bias)
    live = None if live_tokens is None else jnp.arange(S) < live_tokens
    if held is not None:
        experts = {name: leaf[held[0] : held[0] + held[1]] for name, leaf in experts.items()}
    target = jax.random.normal(jax.random.PRNGKey(13), tokens.shape)

    def grads(form):
        _through(form, monkeypatch)

        def loss(e, t, lg):
            out, _, _ = routed_ffn_module.routed_ffn(e, t, lg, k=3, activation=activation, norm_topk_prob=True, live=live, held=held)
            return jnp.sum(out * target) + 0.5 * jnp.sum(out**2)

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(experts, tokens, logits)

    got, want = grads("pallas_interpret"), grads("sorted")
    assert float(jnp.abs(want[2]).max()) > 0 and float(jnp.abs(want[1]).max()) > 0
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5 * max(float(jnp.abs(b).max()), 1e-6), rtol=1e-5)


@pytest.fixture(scope="module")
def v5e():
    """One chip of a described ``v5e:2x2`` (a compile, not a run); the
    persistent compile cache is off around it (what is compiled for a
    described chip can never be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "rows, width, k, held, hidden, inner",
    [(64, 64, 4, (0, 8), 2048, 1536), (64, 256, 10, (0, 16), 3072, 1024), (512, 64, 4, (0, 8), 2048, 1536)],
    ids=["lfm2_narrow", "laguna_narrow", "lfm2_token_tile"],
)
def test_a_compiled_routed_layer_sorts_nothing(v5e, monkeypatch, rows, width, k, held, hidden, inner):
    """``routed_ffn`` as a serving step calls it (``held``, ``live``, sigmoid
    with a bias), compiled for a v5e at LFM2's and Laguna's narrow shapes and
    at a token tile's: on a TPU the plan is the ``moe_route_plan`` kernel, and
    the program holds no ``sort`` at all."""
    import sys

    for module in ("deepspeed_tpu.moe.route_plan", "deepspeed_tpu.moe.grouped_matmul"):
        monkeypatch.setattr(sys.modules[module], "on_tpu", lambda: True)

    def on_v5e(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def layer(experts, tokens, logits, bias, live):
        return routed_ffn_module.routed_ffn(
            experts, tokens, logits, k=k, activation="swiglu", norm_topk_prob=True, live=live, scoring="sigmoid", select_bias=bias, held=held
        )[:2]

    experts = {"w_gate": on_v5e((held[1], hidden, inner), jnp.bfloat16), "w_up": on_v5e((held[1], hidden, inner), jnp.bfloat16), "w_out": on_v5e((held[1], inner, hidden), jnp.bfloat16)}
    text = jax.jit(layer).lower(
        experts, on_v5e((rows, hidden), jnp.bfloat16), on_v5e((rows, width), jnp.float32), on_v5e((width,), jnp.float32), on_v5e((rows,), bool)
    ).compile().as_text()
    kernels = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line and "moe_route/moe_route_plan" in line]
    assert len(kernels) == 1, kernels
    assert not re.findall(r"^\s*(?:ROOT )?%[\w.-]+ = \S+ sort\(", text, flags=re.M), "a sort in a routed layer"


@pytest.mark.parametrize(
    "rows, width, k, held",
    [(8, 64, 8, None), (8, 8, 2, None), (24, 64, 4, (0, 8)), (40, 320, 8, (40, 40)), (72, 256, 10, (16, 16)), (520, 64, 4, (0, 8)), (1000, 64, 8, None)],
    ids=["8_olmoe", "8_of_8", "24_lfm2", "40_solar", "72_laguna", "520_lfm2", "1000_olmoe"],
)
def test_the_kernel_compiles_for_a_v5e_at_the_sizes_it_admits(v5e, rows, width, k, held):
    """``kernel_fits`` admits any whole sublanes of tokens up to 1,024: the
    fewest, sizes that are no multiple of 16 (one block of that many tokens)
    and a ragged last block all pass Mosaic (their RESULTS are held to the
    sorted form on the chip by ``tools/route_plan_bench.py``)."""
    from deepspeed_tpu.moe.route_plan import kernel_fits, route_plan

    assert kernel_fits(rows, width, k)

    def plan(logits, bias, live):
        return route_plan(logits, k=k, norm_topk_prob=True, scoring="sigmoid", select_bias=bias, live=live, held=held, impl="kernel")

    text = jax.jit(plan).lower(
        jax.ShapeDtypeStruct((rows, width), jnp.float32, sharding=v5e), jax.ShapeDtypeStruct((width,), jnp.float32, sharding=v5e),
        jax.ShapeDtypeStruct((rows,), bool, sharding=v5e),
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "moe_route_plan" in text


def test_route_plan_bench_rehearses():
    """``tools/route_plan_bench.py --rehearse``: the tool's control flow, tiny, on the CPU: the kernel held to the
    sorted form and the two ways to the gathers at every shape first, then a line a window, three forms each."""
    import json
    import pathlib
    import subprocess
    import sys

    tool = pathlib.Path(__file__).parents[3] / "tools" / "route_plan_bench.py"
    done = subprocess.run([sys.executable, str(tool), "--rehearse"], capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]
    assert lines[0]["check"] == "kernel == sorted, live_rows == gather" and lines[0]["failed"] == []
    assert [shape[1] for shape in lines[0]["shapes"]] == [16, 64, 8, 24]
    assert [(line["cell"], line["window"], line["device"]) for line in lines[1:]] == [("tiny", "narrow", "cpu"), ("tiny", "mixed", "cpu")]
    for line in lines[1:]:  # times less the empty loop's: on the CPU, two calls, of any sign
        assert all(isinstance(line[form][key], float) for form in ("overhead_us", "sorted", "kernel") for key in ("plan_us", "block_us"))
        assert isinstance(line["live_rows"]["block_us"], float)  # the plan's kernel and the two calls that walk the groups' rows
