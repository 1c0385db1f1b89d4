"""Routing over a router's whole width with only a share of its experts
held (``moe/routed_ffn.py``: ``scoring``, ``select_bias``, ``held``), and the
hybrid model's FFN around it (``models/hybrid_moe.py::moe_ffn``: the shared
expert once). Float32 on the CPU; the program and the brute-force sums differ
by the order of their additions: 2e-6 on outputs of size ~0.05."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.hybrid_moe import (
    HybridMoETransformerLM, glm4_moe_lite_config, kimi_linear_config, laguna_config, mimo_v2_config, moe_ffn, solar_open2_config,
)
from deepspeed_tpu.moe.routed_ffn import route, routed_ffn

S, H, I, E, K = 48, 32, 24, 16, 4
TOL = 2e-6


def _layer(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    experts = {"w_gate": jax.random.normal(ks[0], (E, H, I)) * 0.2, "w_up": jax.random.normal(ks[1], (E, H, I)) * 0.2,
               "w_out": jax.random.normal(ks[2], (E, I, H)) * 0.2}
    tokens = jax.random.normal(ks[3], (S, H))
    logits = jax.random.normal(ks[4], (S, E)) * 1.3
    bias = jax.random.normal(ks[5], (E,)) * 0.3
    return experts, tokens, logits, bias


def _brute(experts, tokens, weights):
    """sum_e weights[:, e] * SwiGLU_e(tokens): every expert on every token."""
    out = 0
    for e in range(experts["w_gate"].shape[0]):
        y = (jax.nn.silu(tokens @ experts["w_gate"][e]) * (tokens @ experts["w_up"][e])) @ experts["w_out"][e]
        out = out + weights[:, e, None] * y
    return out


def _weights(logits, bias, k=K):
    s = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(s + bias, k)
    top = jnp.take_along_axis(s, chosen, -1)
    top = top / top.sum(-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(chosen, logits.shape[-1]) * top[..., None], axis=-2)


def test_sigmoid_routing_picks_by_score_plus_bias_and_weighs_by_score():
    _, _, logits, bias = _layer()
    gates, chosen, top = route(logits, K, True, scoring="sigmoid", select_bias=bias)
    assert np.allclose(gates, jax.nn.sigmoid(logits))
    want = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, K)[1]
    assert np.array_equal(np.sort(chosen, -1), np.sort(want, -1))
    assert not np.array_equal(np.sort(chosen, -1), np.sort(jax.lax.top_k(logits, K)[1], -1))  # the bias moved a choice
    unbiased = jnp.take_along_axis(jax.nn.sigmoid(logits), chosen, -1)
    assert np.allclose(top, unbiased / unbiased.sum(-1, keepdims=True), atol=1e-6)
    with pytest.raises(ValueError, match="unknown router scoring"):
        route(logits, K, True, scoring="tanh")


def test_softmax_routing_is_what_it_was():
    """The defaults are the old function: same choices, same gates."""
    _, _, logits, _ = _layer()
    gates, chosen, top = route(logits, K, False)
    assert np.allclose(gates, jax.nn.softmax(logits, -1), atol=1e-7)
    assert np.array_equal(chosen, jax.lax.top_k(gates, K)[1])
    assert np.allclose(top, jnp.take_along_axis(gates, chosen, -1))


@pytest.mark.parametrize("of", [1, 2, 8])
def test_the_shares_routed_parts_add_up_to_the_uncut_layer(of):
    """Each of ``of`` chips holds ``E / of`` experts and computes its own part
    of the k-term sum, gates normalised over all k; the parts add up to the
    whole layer's output, and each chip's counts are its experts' own."""
    experts, tokens, logits, bias = _layer(of)
    live = jnp.arange(S) % 7 != 3
    whole = _brute(experts, tokens, _weights(logits, bias)) * live[:, None]
    n = E // of
    parts, counted = 0, []
    for index in range(of):
        mine = jax.tree_util.tree_map(lambda a: a[index * n : (index + 1) * n], experts)
        out, counts, _ = routed_ffn(mine, tokens, logits, k=K, activation="swiglu", norm_topk_prob=True, live=live,
                                    scoring="sigmoid", select_bias=bias, held=(index * n, n))
        assert counts.shape == (n,)
        parts, counted = parts + out, counted + [counts]
    assert float(jnp.abs(parts - whole).max()) < TOL
    chosen = jax.lax.top_k(jax.nn.sigmoid(logits) + bias, K)[1][live]
    assert np.array_equal(np.concatenate(counted), np.bincount(np.asarray(chosen).reshape(-1), minlength=E))


def test_eight_shares_plus_the_shared_expert_once_are_the_uncut_model_layer():
    """The model's own FFN (``moe_ffn``) on a seeded layer: eight configs that
    differ in ``moe_expert_share`` alone, each given its slice of the uncut
    layer's expert stacks; the routed parts (each share's output minus the
    shared expert's) plus the shared expert counted ONCE equal the uncut
    layer, which is also what the plain reference computes."""
    whole_cfg = solar_open2_config("tiny", num_experts=16, moe_router_experts=16, moe_expert_share=(0, 1), moe_top_k=4, dtype="float32")
    lm = HybridMoETransformerLM(whole_cfg)
    p = jax.tree_util.tree_map(lambda a: a[0, 1], lm.init(jax.random.PRNGKey(3), None)["periods"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, whole_cfg.hidden_size))
    whole, counts = moe_ffn(whole_cfg, p, h)
    assert int(counts.sum()) == 2 * 24 * 4
    from deepspeed_tpu.moe.experts import apply_dense_ffn

    shared = apply_dense_ffn(p["shared"], h, "swiglu")
    total = shared
    for index in range(8):
        cfg = dataclasses.replace(whole_cfg, num_experts=2, moe_expert_share=(index, 8))
        mine = {**p, "experts": jax.tree_util.tree_map(lambda a: a[index * 2 : index * 2 + 2], p["experts"])}
        out, held = moe_ffn(cfg, mine, h)
        assert held.shape == (2,) and np.array_equal(held, counts[index * 2 : index * 2 + 2])
        total = total + (out - shared)
    assert float(jnp.abs(total - whole).max()) < TOL
    tokens = h.reshape(-1, whole_cfg.hidden_size)
    brute = _brute(p["experts"], tokens, _weights(tokens @ p["gate"]["wg"], p["gate"]["bias"])).reshape(h.shape) + shared
    assert float(jnp.abs(brute - whole).max()) < TOL


def test_sixteen_shares_without_a_shared_expert_are_the_uncut_model_layer():
    """The first share with nothing that every chip computes alike: sixteen
    configs that differ in ``moe_expert_share`` alone, each given its slice of
    the uncut layer's expert stacks (2 of 32 experts, 8 a token); the sixteen
    outputs add up to the uncut layer with nothing to count once, a token
    whose 8 choices all lie elsewhere gets exact zeros from a share, and the
    plain reference's loop over a share's held experts gives that share's part."""
    from benchmark.files import load_module

    kw = dict(num_experts=32, moe_router_experts=32, moe_expert_share=(0, 1), moe_top_k=8, dtype="float32")
    whole_cfg = mimo_v2_config("tiny", **kw)
    assert whole_cfg.moe_shared_experts == 0
    lm = HybridMoETransformerLM(whole_cfg)
    p = jax.tree_util.tree_map(lambda a: a[0, 2], lm.init(jax.random.PRNGKey(3), None)["periods"]["moe"])
    assert "shared" not in p
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, whole_cfg.hidden_size))
    whole, counts = moe_ffn(whole_cfg, p, h)
    assert int(counts.sum()) == 2 * 24 * 8
    tokens = h.reshape(-1, whole_cfg.hidden_size)
    weights = _weights(tokens @ p["gate"]["wg"], p["gate"]["bias"], k=8)
    assert float(jnp.abs(_brute(p["experts"], tokens, weights).reshape(h.shape) - whole).max()) < TOL
    total, untouched = 0, 0
    for index in range(16):
        cfg = dataclasses.replace(whole_cfg, num_experts=2, moe_expert_share=(index, 16))
        mine = {**p, "experts": jax.tree_util.tree_map(lambda a: a[index * 2 : index * 2 + 2], p["experts"])}
        out, held = moe_ffn(cfg, mine, h)
        assert held.shape == (2,) and np.array_equal(held, counts[index * 2 : index * 2 + 2])
        part = _brute(mine["experts"], tokens, weights[:, index * 2 : index * 2 + 2]).reshape(h.shape)
        assert float(jnp.abs(out - part).max()) < TOL
        nothing_here = np.asarray(weights[:, index * 2 : index * 2 + 2].sum(-1) == 0).reshape(h.shape[:2])
        assert np.all(np.asarray(out)[nothing_here] == 0.0)
        untouched += int(nothing_here.sum())
        total = total + out
    assert untouched > 0
    assert float(jnp.abs(total - whole).max()) < TOL
    # the reference's routed FFN for one share: its router over the whole width, its loop over the held
    ref = load_module("reference", "mimo_v2_decoder")
    x = jnp.zeros_like(tokens)
    gate = {"mlp_norm_scale": jnp.ones((whole_cfg.hidden_size,)), "gate": p["gate"]}
    hn, w = ref._router(tokens, gate, arch_key=(("experts_per_token", 8), ("norm_eps", 1e-5), ("routed_scaling", 1.0)))
    mine = jax.tree_util.tree_map(lambda a: a[6:8], p["experts"])
    out = x
    for e in range(2):
        out = ref._add_expert(out, hn, w[..., 6 + e], mine["w_gate"][e], mine["w_up"][e], mine["w_out"][e])
    norm = tokens * jax.lax.rsqrt(jnp.mean(tokens * tokens, -1, keepdims=True) + 1e-5)
    share3, _ = moe_ffn(dataclasses.replace(whole_cfg, num_experts=2, moe_expert_share=(3, 16)), {**p, "experts": mine}, norm.reshape(h.shape))
    assert float(jnp.abs(out.reshape(h.shape) - share3).max()) < TOL


def test_eight_shares_of_a_scaled_router_plus_the_shared_expert_once_are_the_uncut_model_layer():
    """The latent model's FFN: top-4 of 64 by sigmoid scores with a selection
    bias, weights normalised over the four and times 1.8, one shared expert.
    Eight configs that differ in ``moe_expert_share`` alone, each given its 8
    of the uncut layer's 64 experts: the routed parts (each share's output
    minus the shared expert's) plus the shared expert counted ONCE are the
    uncut layer, which is 1.8 times the brute-force weighted sum plus the
    shared expert; and the plain reference's router and loop over a share's
    held experts give that share's part, the factor inside the weights."""
    from benchmark.files import load_module
    from deepspeed_tpu.moe.experts import apply_dense_ffn

    whole_cfg = glm4_moe_lite_config("tiny", num_experts=64, moe_router_experts=64, moe_expert_share=(0, 1), moe_top_k=4, dtype="float32")
    assert (whole_cfg.moe_routed_scaling, whole_cfg.moe_shared_experts, whole_cfg.moe_scoring) == (1.8, 1, "sigmoid")
    lm = HybridMoETransformerLM(whole_cfg)
    p = jax.tree_util.tree_map(lambda a: a[1, 0], lm.init(jax.random.PRNGKey(3), None)["periods"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, whole_cfg.hidden_size))
    whole, counts = moe_ffn(whole_cfg, p, h)
    assert int(counts.sum()) == 2 * 24 * 4
    shared = apply_dense_ffn(p["shared"], h, "swiglu")
    tokens = h.reshape(-1, whole_cfg.hidden_size)
    weights = 1.8 * _weights(tokens @ p["gate"]["wg"], p["gate"]["bias"])
    assert float(jnp.abs(_brute(p["experts"], tokens, weights).reshape(h.shape) + shared - whole).max()) < TOL
    # without the factor the layer is another one, by far more than the tolerance
    assert float(jnp.abs(_brute(p["experts"], tokens, weights / 1.8).reshape(h.shape) + shared - whole).max()) > 100 * TOL
    total = shared
    for index in range(8):
        cfg = dataclasses.replace(whole_cfg, num_experts=8, moe_expert_share=(index, 8))
        mine = {**p, "experts": jax.tree_util.tree_map(lambda a: a[index * 8 : index * 8 + 8], p["experts"])}
        out, held = moe_ffn(cfg, mine, h)
        assert held.shape == (8,) and np.array_equal(held, counts[index * 8 : index * 8 + 8])
        part = _brute(mine["experts"], tokens, weights[:, index * 8 : index * 8 + 8]).reshape(h.shape)
        assert float(jnp.abs(out - shared - part).max()) < TOL
        total = total + (out - shared)
    assert float(jnp.abs(total - whole).max()) < TOL
    # the reference's routed FFN for share 5: its router over the whole width, its loop over the 8 held
    ref = load_module("reference", "glm4_moe_lite_decoder")
    gate = {"mlp_norm_scale": jnp.ones((whole_cfg.hidden_size,)), "gate": p["gate"], "shared": p["shared"]}
    hn, w, out = ref._router(tokens, gate, arch_key=(("experts_per_token", 4), ("norm_eps", 1e-5), ("routed_scaling", 1.8)))
    mine = jax.tree_util.tree_map(lambda a: a[40:48], p["experts"])
    for e in range(8):
        out = ref._add_expert(out, hn, w[..., 40 + e], mine["w_gate"][e], mine["w_up"][e], mine["w_out"][e])
    share5, _ = moe_ffn(dataclasses.replace(whole_cfg, num_experts=8, moe_expert_share=(5, 8)), {**p, "experts": mine}, hn.reshape(h.shape))
    assert float(jnp.abs(out.reshape(h.shape) - share5).max()) < TOL


def test_a_share_that_is_not_a_share_is_refused():
    with pytest.raises(ValueError, match="holds"):
        solar_open2_config("tiny", num_experts=3, moe_router_experts=8, moe_expert_share=(0, 2))
    with pytest.raises(ValueError, match="layer_types"):
        solar_open2_config("tiny", layer_types=["softmax", "mamba", "linear", "linear"])  # a kind that does not exist


def test_sixteen_shares_of_a_softmax_router_plus_the_shared_expert_once_are_the_uncut_model_layer():
    """Laguna's FFN: top-5 of 32 by SOFTMAX scores over the router's whole
    width (the tiny size's equivalent of top-10 of 256), no selection bias,
    weights normalised over the five and times 2.5, one shared expert. Sixteen
    configs that differ in ``moe_expert_share`` alone, each given its 2 of the
    uncut layer's 32 experts: the routed parts (each share's output minus the
    shared expert's) plus the shared expert counted ONCE are the uncut layer,
    which is the brute-force sum with weights ``2.5 p_e / sum of the chosen p``
    plus the shared expert; and the plain reference's router and loop over a
    share's held experts give that share's part."""
    from benchmark.files import load_module
    from deepspeed_tpu.moe.experts import apply_dense_ffn

    whole_cfg = laguna_config("tiny", num_experts=32, moe_router_experts=32, moe_expert_share=(0, 1), moe_top_k=5, dtype="float32")
    assert (whole_cfg.moe_routed_scaling, whole_cfg.moe_shared_experts, whole_cfg.moe_scoring, whole_cfg.moe_select_bias) == (2.5, 1, "softmax", False)
    lm = HybridMoETransformerLM(whole_cfg)
    p = jax.tree_util.tree_map(lambda a: a[1, 2], jax.jit(lambda key: lm.init(key, None)["periods"]["moe"])(jax.random.PRNGKey(3)))
    assert "bias" not in p["gate"]
    p = {**p, "gate": {"wg": p["gate"]["wg"] * 6.0}}  # router logits of std ~1 (0.02 x 6 x sqrt(64)), as at the published width
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, whole_cfg.hidden_size))
    whole, counts = moe_ffn(whole_cfg, p, h)
    assert int(counts.sum()) == 2 * 24 * 5
    shared = apply_dense_ffn(p["shared"], h, "swiglu")
    tokens = h.reshape(-1, whole_cfg.hidden_size)
    probs = jax.nn.softmax(tokens @ p["gate"]["wg"], axis=-1)
    top, chosen = jax.lax.top_k(probs, 5)
    weights = jnp.sum(jax.nn.one_hot(chosen, 32) * (2.5 * top / top.sum(-1, keepdims=True))[..., None], axis=-2)
    assert float(weights.sum(-1).min()) == pytest.approx(2.5, rel=1e-5) and float(top.std()) > 0.01
    assert float(jnp.abs(_brute(p["experts"], tokens, weights).reshape(h.shape) + shared - whole).max()) < TOL
    # sigmoid scores, or weights that are not renormalised, are another layer by far more than the tolerance
    other = _brute(p["experts"], tokens, jnp.sum(jax.nn.one_hot(chosen, 32) * (2.5 * top)[..., None], axis=-2)).reshape(h.shape)
    assert float(jnp.abs(other + shared - whole).max()) > 0.2 * float(jnp.abs(whole).max()) > 100 * TOL
    total = shared
    for index in range(16):
        cfg = dataclasses.replace(whole_cfg, num_experts=2, moe_expert_share=(index, 16))
        mine = {**p, "experts": jax.tree_util.tree_map(lambda a: a[index * 2 : index * 2 + 2], p["experts"])}
        out, held = moe_ffn(cfg, mine, h)
        assert held.shape == (2,) and np.array_equal(held, counts[index * 2 : index * 2 + 2])
        part = _brute(mine["experts"], tokens, weights[:, index * 2 : index * 2 + 2]).reshape(h.shape)
        assert float(jnp.abs(out - shared - part).max()) < TOL
        total = total + (out - shared)
    assert float(jnp.abs(total - whole).max()) < TOL
    # the reference's routed FFN for one share: its router over the whole width (the factor inside the weights,
    # the shared expert beside them), its loop over the held
    ref = load_module("reference", "laguna_decoder")
    gate = {"mlp_norm_scale": jnp.ones((whole_cfg.hidden_size,)), "gate": p["gate"], "shared": p["shared"]}
    hn, w, out = ref._router(tokens, gate, arch_key=(("experts_per_token", 5), ("norm_eps", 1e-6), ("routed_scaling", 2.5)))
    mine = jax.tree_util.tree_map(lambda a: a[6:8], p["experts"])
    for e in range(2):
        out = ref._add_expert(out, hn, w[..., 6 + e], mine["w_gate"][e], mine["w_up"][e], mine["w_out"][e])
    norm = tokens * jax.lax.rsqrt(jnp.mean(tokens * tokens, -1, keepdims=True) + 1e-6)
    share3, _ = moe_ffn(dataclasses.replace(whole_cfg, num_experts=2, moe_expert_share=(3, 16)), {**p, "experts": mine}, norm.reshape(h.shape))
    assert float(jnp.abs(out.reshape(h.shape) - share3).max()) < TOL


@pytest.mark.parametrize("share", [0, 3, 7])
def test_eight_shares_of_a_router_over_256_plus_the_shared_expert_once_are_the_uncut_model_layer(share):
    """Kimi-Linear's FFN at its router's PUBLISHED width: top-8 of 256 by
    sigmoid scores with a selection bias, weights normalised over the eight and
    times 2.446, one shared expert. Eight configs that differ in
    ``moe_expert_share`` alone, each given its 32 of the uncut layer's 256
    experts: the routed parts (each share's output minus the shared expert's)
    plus the shared expert counted ONCE are the uncut layer, which is 2.446
    times the brute-force weighted sum plus the shared expert; and the plain
    reference's router and loop over ``share``'s 32 held experts give that
    share's part, the factor inside the weights."""
    from benchmark.files import load_module
    from deepspeed_tpu.moe.experts import apply_dense_ffn

    whole_cfg = kimi_linear_config("tiny", num_experts=256, moe_router_experts=256, moe_expert_share=(0, 1), moe_top_k=8, dtype="float32")
    assert (whole_cfg.moe_routed_scaling, whole_cfg.moe_shared_experts, whole_cfg.moe_scoring, whole_cfg.moe_select_bias) == (2.446, 1, "sigmoid", True)
    lm = HybridMoETransformerLM(whole_cfg)
    p = jax.tree_util.tree_map(lambda a: a[1, 2], jax.jit(lambda key: lm.init(key, None)["periods"]["moe"])(jax.random.PRNGKey(3)))
    p = {**p, "gate": {"wg": p["gate"]["wg"] * 6.0, "bias": p["gate"]["bias"]}}  # router logits of std ~1, as at the published width
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, whole_cfg.hidden_size))
    whole, counts = moe_ffn(whole_cfg, p, h)
    assert counts.shape == (256,) and int(counts.sum()) == 2 * 24 * 8
    shared = apply_dense_ffn(p["shared"], h, "swiglu")
    tokens = h.reshape(-1, whole_cfg.hidden_size)
    weights = 2.446 * _weights(tokens @ p["gate"]["wg"], p["gate"]["bias"], k=8)
    assert float(jnp.abs(_brute(p["experts"], tokens, weights).reshape(h.shape) + shared - whole).max()) < TOL
    # without the factor the layer is another one, by far more than the tolerance
    assert float(jnp.abs(_brute(p["experts"], tokens, weights / 2.446).reshape(h.shape) + shared - whole).max()) > 100 * TOL
    total = shared
    for index in range(8):
        cfg = dataclasses.replace(whole_cfg, num_experts=32, moe_expert_share=(index, 8))
        mine = {**p, "experts": jax.tree_util.tree_map(lambda a: a[index * 32 : index * 32 + 32], p["experts"])}
        out, held = moe_ffn(cfg, mine, h)
        assert held.shape == (32,) and np.array_equal(held, counts[index * 32 : index * 32 + 32])
        total = total + (out - shared)
        if index == share:
            mine_out = out
    assert float(jnp.abs(total - whole).max()) < TOL
    # the reference's routed FFN for this share: its router over the whole width, its loop over the 32 held
    ref = load_module("reference", "kimi_linear_decoder")
    gate = {"mlp_norm_scale": jnp.ones((whole_cfg.hidden_size,)), "gate": p["gate"], "shared": p["shared"]}
    hn, w, out = ref._router(tokens, gate, arch_key=(("experts_per_token", 8), ("norm_eps", 1e-5), ("routed_scaling", 2.446)))
    first = share * 32
    for e in range(32):
        out = ref._add_expert(out, hn, w[..., first + e], *(p["experts"][name][first + e] for name in ("w_gate", "w_up", "w_out")))
    held_cfg = dataclasses.replace(whole_cfg, num_experts=32, moe_expert_share=(share, 8))
    mine = {**p, "experts": jax.tree_util.tree_map(lambda a: a[first : first + 32], p["experts"])}
    normed, _ = moe_ffn(held_cfg, mine, hn.reshape(h.shape))
    assert float(jnp.abs(out.reshape(h.shape) - normed).max()) < TOL
    assert float(jnp.abs(mine_out - shared).max()) > 100 * TOL  # the share's routed part is no rounding
