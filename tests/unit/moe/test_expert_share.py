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

from deepspeed_tpu.models.hybrid_moe import HybridMoETransformerLM, moe_ffn, solar_open2_config
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


def test_a_share_that_is_not_a_share_is_refused():
    with pytest.raises(ValueError, match="holds"):
        solar_open2_config("tiny", num_experts=3, moe_router_experts=8, moe_expert_share=(0, 2))
    with pytest.raises(ValueError, match="layer_types"):
        solar_open2_config("tiny", layer_types=["softmax", "window", "linear", "linear"])
