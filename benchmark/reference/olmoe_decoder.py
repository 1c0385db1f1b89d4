"""The plain reference of the OLMoE block (Muennighoff et al. 2024, "OLMoE:
Open Mixture-of-Experts Language Models"; HF ``OlmoeModel``): the forward
pass and next-token loss in straightforward ``jax.numpy`` and float32,
matrix multiplications at precision ``highest``. No kernel, no cache, no
sort, no capacity, no scan; nothing imported from the program under test.

One layer, as published:

    h = RMSNorm(x)                                   (input_layernorm)
    q, k, v = h Wq, h Wk, h Wv                        (no biases)
    q, k = RMSNorm_q(q), RMSNorm_k(k)                 over the WHOLE projection
                                                      (all heads at once), before
                                                      the split into heads
    q, k = RoPE(q), RoPE(k)                           half-split ("rotate_half"),
                                                      base rope_theta
    x = x + softmax(causal(q k^T / sqrt(d))) v Wo
    h = RMSNorm(x)                                   (post_attention_layernorm)
    g = softmax(h Wr) over all E experts, float32
    top = the k largest of g                          not renormalised unless
                                                      norm_topk_prob
    x = x + sum_{e in top} g_e * (silu(h Wgate_e) * (h Wup_e)) Wdown_e

then a final RMSNorm and the untied head. The experts are a plain loop: every
expert is applied to every token and weighted by ``g_e`` where e is among the
token's k, by zero elsewhere, one expert's weights in float32 at a time, so
that the reference fits beside the server on one chip.

Same interface as every reference: ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``, ``model`` being the configuration file's
``model`` section, weights in the program's own tree (``layers/wq``,
``layers/q_norm_scale``, ``layers/moe/gate/wg``, ``layers/moe/experts/w_gate``
``[L, E, H, I]``, ...), because that is where the seeded weights live.

Departures from the published code: none in the mathematics. ``clip_qkv`` is
null in this model and not implemented; the router's auxiliary loss is a
training term and is not part of ``loss``; HF casts the k chosen gates to the
activations' type before weighting, which in float32 is the identity.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x [B, T, N, D]; position t rotates pair (i, i + D/2) by ``t * theta**(-2i/D)``."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _attention_and_router(x, stacks, layer, arch_key):
    """Layer ``layer``'s attention block, then the second norm and the
    router: returns (x after attention, the normed input of the experts, each
    token's weight for each expert [B, T, E]: its gate where chosen, zero
    elsewhere). ``stacks`` are the per-layer weights with their leading
    layer axis; the layer is an argument, so one compilation serves all."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a[layer].astype(F32), stacks)
    B, T, H = x.shape
    N, D, eps = arch["num_heads"], arch["head_dim"], arch["norm_eps"]
    h = _rms(x, p["attn_norm_scale"], eps)
    q, k, v = h @ p["wq"], h @ p["wk"], h @ p["wv"]
    q, k = _rms(q, p["q_norm_scale"], eps), _rms(k, p["k_norm_scale"], eps)
    q, k, v = q.reshape(B, T, N, D), k.reshape(B, T, N, D), v.reshape(B, T, N, D)
    q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    scores = jnp.einsum("btnd,bsnd->bnts", q, k) / jnp.sqrt(F32(D))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    x = x + jnp.einsum("bnts,bsnd->btnd", probs, v).reshape(B, T, N * D) @ p["wo"]
    h = _rms(x, p["mlp_norm_scale"], eps)
    gates = jax.nn.softmax(h @ p["wg"], axis=-1)
    top, chosen = jax.lax.top_k(gates, arch["experts_per_token"])
    if arch["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(chosen, gates.shape[-1], dtype=F32) * top[..., None], axis=-2)
    return x, h, weights


@jax.jit
def _add_expert(acc, h, weights, experts, layer, e):
    """acc + weight_e * expert_e(h) for expert ``e`` of ``layer``, every
    token; only that expert's three matrices are upcast."""
    w_gate, w_up, w_down = (experts[name][layer, e].astype(F32) for name in ("w_gate", "w_up", "w_out"))
    return acc + weights[..., e, None] * ((jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, head, eps):
    return _rms(x, scale.astype(F32), eps) @ head.astype(F32)


def arch_of(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference needs of a configuration file's ``model``
    section; refuses a block this file does not describe."""
    kw = model["kwargs"]
    heads = kw["num_heads"]
    arch = {
        "num_layers": kw["num_layers"],
        "num_heads": heads,
        "head_dim": kw.get("head_dim") or kw["hidden_size"] // heads,
        "norm_eps": kw["norm_eps"],
        "rope_theta": float(kw.get("rope_theta", 10000.0)),
        "num_experts": kw["num_experts"],
        "experts_per_token": kw["moe_top_k"],
        "norm_topk_prob": bool(kw["moe_norm_topk_prob"]),
    }
    described = (
        kw["norm"] == "rmsnorm" and kw["position"] == "rope" and kw["activation"] == "swiglu" and kw.get("qk_norm") == "projection"
        and (kw.get("num_kv_heads") or heads) == heads and not kw.get("use_bias", False) and not kw["tie_embeddings"]
        and kw.get("moe_layer_freq", 1) == 1 and not kw.get("use_residual", False) and kw.get("moe_drop_tokens") is False
    )
    if not described:
        raise ValueError(f"the OLMoE reference does not describe {kw}")
    return arch


def logits(model: Dict[str, Any], params, tokens):
    """tokens [B, T] int32 -> float32 logits [B, T, vocab]."""
    arch = arch_of(model)
    key = tuple(sorted(arch.items()))
    layers = params["layers"]
    stacks = {k: v for k, v in layers.items() if k != "moe"}
    stacks["wg"] = layers["moe"]["gate"]["wg"]
    experts = {name: layers["moe"]["experts"][name] for name in ("w_gate", "w_up", "w_out")}
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        for i in range(arch["num_layers"]):
            x, h, weights = _attention_and_router(x, stacks, jnp.int32(i), arch_key=key)
            out = jnp.zeros_like(x)
            for e in range(arch["num_experts"]):
                out = _add_expert(out, h, weights, experts, jnp.int32(i), jnp.int32(e))
            x = x + out
        return _head(x, params["final_norm_scale"], params["lm_head"], eps=arch["norm_eps"])


def loss(model: Dict[str, Any], params, tokens):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1]."""
    lg = logits(model, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
