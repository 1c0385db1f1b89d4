"""The plain reference of GLM-4.7-Flash's block (``zai-org/GLM-4.7-Flash``
``config.json``, ``model_type: glm4_moe_lite``; the catalog describes it as
"MLA - 47L; 64 experts, top-4, 1 shared; scaling 1.8; MTP 1"): the forward pass
and next-token loss in straightforward ``jax.numpy`` and float32, matrix
multiplications at precision ``highest``, in the PUBLISHED (expanded) form:
every head's keys and values are made from the latents of the whole sequence.
No kernel, no cache, no pages, no absorbed product, no tiles, no sort: the mask
is built from positions, the experts are a loop one expert at a time, a
layer's weights are upcast a layer at a time. Nothing is imported from the
program under test.

``x`` is the residual stream; pre-norm, two sub-blocks a layer:
``x += mixer(RMSNorm(x))``, ``x += ffn(RMSNorm(x))`` (``rms_norm_eps``), no
biases, a final RMSNorm and the untied head. The first ``first_k_dense_replace``
layers have a dense FFN, the others the routed one.

The mixer, ``h = RMSNorm(x)``, token ``i`` at absolute position ``i``, head ``n``
of 20:

    c_q = RMSNorm(h Wq_a)                        [768]   (q_lora_rank)
    [q_nope_n (192) ; q_rope_n (64)] = c_q Wq_b,n
    [c ; r] = h Wkv_a                            [512 + 64]
    c_kv = RMSNorm(c)     the 512 alone          k_rope = RoPE(r, i)   ONE, shared by all heads
    k_nope_n = c_kv Wk_b,n   [192]               v_n = c_kv Wv_b,n   [256]
    s_n(i, j) = (q_nope_n,i . k_nope_n,j + RoPE(q_rope_n,i, i) . k_rope_j) / sqrt(256),   j <= i
    o_n,i = sum_j softmax_j(s_n(i, .)) v_n,j     mixer = concat_n(o_n) Wo      Wo [20 x 256, 2048]

    RoPE: rotate-half inside the 64 (feature j pairs with j + 32, angle
    i * theta^(-j / 32)), theta 1e6, no scaling.

Dense FFN (layer 0): ``(silu(h Wg) * (h Wu)) Wd``, width 10,240. Routed FFN
(layers >= 1), ``h = RMSNorm(x)``:

    s = sigmoid(h Wr)                           float32, over ALL 64 routed experts
    top = the 4 largest of s + bias             the bias picks, it does not weigh
    w_e = 1.8 s_e / sum of the 4 chosen s       (norm_topk_prob, routed_scaling_factor)
    ffn = sum_{e in top} w_e SwiGLU_e(h) + SwiGLU_shared(h)     widths 1,536

DEPARTURES from the published description, each also under ``assumed`` in the
configuration file:

* the published ``kv_b_proj`` maps ``c_kv`` to ``[k_nope_n ; v_n]`` a head; the
  program's tree stores its two parts apart (``wk_b`` [512, 20 x 192], ``wv_b``
  [512, 20 x 256]) and this file reads them so: the same products;
* the rotary pairing inside the 64 is rotate-half (with seeded weights the
  interleaved pairing is a permutation of features);
* the softmax scale is ``256^-0.5`` = (192 + 64)^-0.5 with no ``mscale``
  (``rope_scaling`` null); the kv norm is over the 512 alone; the router is
  float32; the selection bias (``topk_method`` noaux_tc) is used for the choice
  alone; ``n_group`` = ``topk_group`` = 1 is no group limit;
* ``head_dim`` (null in the catalog) is 256, the query/key head's two parts.

LEFT OUT, by name: the multi-token-prediction layer (``num_nextn_predict_layers``
1): a served step yields one token a row.

THE SHARE. The ``model`` section may hold one chip's share of a deployment
(``moe_expert_share = (index, of)``, ``num_experts`` held of
``moe_router_experts``): the router keeps its whole width and its 4 a token,
the weights are normalised over all 4, and only the held experts' terms are
summed, with no stand-in for the absent ones; the shared expert is whole. The
vocabulary may be a slice; embedding and head are then that slice.

Same interface as every reference: ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``; weights in the program's own tree
(``leading[i]/mixer``, ``leading[i]/ffn``, ``periods/latent/...``,
``periods/moe/...``, the periods' leaves ``[periods, layers a period, ...]``).
Computed a sequence at a time and a head's scores at a time, so that
``[4, 2048]`` tokens fit beside a resident serving program: a head's float32
scores are ``2048 x 2048 x 4 B`` = 17 MB.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate(x, theta):
    """``x`` [T, ..., D], token ``i`` at position ``i``: every feature rotated,
    feature ``j`` with ``j + D / 2``."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * theta ** (-jnp.arange(half, dtype=F32) / half)  # [T, half]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle), a * jnp.sin(angle) + b * jnp.cos(angle)], axis=-1)


def arch_of(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference needs of a configuration file's ``model``
    section; refuses a block this file does not describe."""
    kw = model["kwargs"]
    types = tuple(kw["layer_types"])
    index, of = kw.get("moe_expert_share", (0, 1))
    arch = {
        "layers": len(types),
        "leading": kw["leading_dense_layers"],
        "num_heads": kw["num_heads"],
        "nope": kw["qk_nope_head_dim"],
        "rope": kw["qk_rope_head_dim"],
        "v_head_dim": kw["v_head_dim"],
        "kv_lora_rank": kw["kv_lora_rank"],
        "theta": float(kw["rope_theta"]),
        "norm_eps": kw["norm_eps"],
        "held": kw["num_experts"],
        "first_held": index * kw["num_experts"],
        "experts_per_token": kw["moe_top_k"],
        "routed_scaling": float(kw["moe_routed_scaling"]),
    }
    described = (
        len(types) == kw["num_layers"] and set(types) == {"latent"} and 0 <= arch["leading"] < len(types)
        and kw["head_dim"] == arch["nope"] + arch["rope"] and kw.get("attn_softmax_scale") is None
        and kw["norm"] == "rmsnorm" and kw["position"] == "rope" and kw["activation"] == "swiglu"
        and not kw.get("use_bias", False) and not kw["tie_embeddings"] and kw["moe_scoring"] == "sigmoid"
        and kw["moe_select_bias"] is True and kw["moe_norm_topk_prob"] is True and kw["moe_shared_experts"] == 1
        and kw.get("moe_drop_tokens") is False and kw["num_experts"] * of == kw["moe_router_experts"]
    )
    if not described:
        raise ValueError(f"the GLM-4 MoE Lite reference does not describe {kw}")
    return arch


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _mixer(x, p, arch_key):
    """One sequence ``x`` [T, H] through a latent layer's mixer, expanded."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    T = x.shape[0]
    N, nope, rope, Dv, C = arch["num_heads"], arch["nope"], arch["rope"], arch["v_head_dim"], arch["kv_lora_rank"]
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    q = (_rms(h @ p["wq_a"], p["q_norm_scale"], arch["norm_eps"]) @ p["wq_b"]).reshape(T, N, nope + rope)
    q_nope, q_rope = q[..., :nope], _rotate(q[..., nope:], arch["theta"])
    kv = h @ p["wkv_a"]
    c_kv = _rms(kv[:, :C], p["kv_norm_scale"], arch["norm_eps"])  # the norm over the latent alone
    k_rope = _rotate(kv[:, C:], arch["theta"])  # [T, rope]: one for all heads
    k_nope = (c_kv @ p["wk_b"]).reshape(T, N, nope)
    v = (c_kv @ p["wv_b"]).reshape(T, N, Dv)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def one_head(args):
        qn, qr, kn, vh = args  # [T, nope], [T, rope], [T, nope], [T, Dv]
        scores = (qn @ kn.T + qr @ k_rope.T) / jnp.sqrt(F32(nope + rope))
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ vh

    heads = lambda a: a.transpose(1, 0, 2)
    attn = jax.lax.map(one_head, (heads(q_nope), heads(q_rope), heads(k_nope), heads(v)))  # [N, T, Dv]
    return x + attn.transpose(1, 0, 2).reshape(T, N * Dv) @ p["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, p, eps):
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    h = _rms(x, p["mlp_norm_scale"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_out"]


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _router(x, p, arch_key):
    """The second norm, each token's weight for each routed expert [T, E] (1.8
    times its normalised score where chosen, zero elsewhere) and the shared
    expert's output."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    h = _rms(x, p["mlp_norm_scale"], arch["norm_eps"])
    s = jax.nn.sigmoid(h @ p["gate"]["wg"])
    _, chosen = jax.lax.top_k(s + p["gate"]["bias"], arch["experts_per_token"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) * arch["routed_scaling"]
    weights = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32) * top[..., None], axis=-2)
    shared = (jax.nn.silu(h @ p["shared"]["w_gate"]) * (h @ p["shared"]["w_up"])) @ p["shared"]["w_out"]
    return h, weights, shared


@jax.jit
def _add_expert(acc, h, weight, w_gate, w_up, w_down):
    """acc + weight * expert(h), every token; one expert's matrices upcast."""
    return acc + weight[..., None] * ((jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) @ w_down.astype(F32))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, head, eps):
    return _rms(x, scale.astype(F32), eps) @ head.astype(F32)


def _sequence(arch, key, params, tokens):
    """One sequence ``tokens`` [T] -> logits [T, V]."""
    periods = params["periods"]  # a period is one layer: leaves [periods, 1, ...]
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i, 0], tree)
    x = params["embed"]["tokens"][tokens].astype(F32)
    for p in params.get("leading", ()):
        x = _mixer(x, p["mixer"], arch_key=key)
        x = _dense_ffn(x, p["ffn"], eps=arch["norm_eps"])
    for i in range(arch["layers"] - arch["leading"]):
        x = _mixer(x, at(periods["latent"], i), arch_key=key)
        moe = periods["moe"]
        h, weights, out = _router(x, at({k: v for k, v in moe.items() if k != "experts"}, i), arch_key=key)
        for e in range(arch["held"]):  # the held experts' terms of the 4-term sum
            w = (moe["experts"][name][i, 0, e] for name in ("w_gate", "w_up", "w_out"))
            out = _add_expert(out, h, weights[..., arch["first_held"] + e], *w)
        x = x + out
    return _head(x, params["final_norm_scale"], params["lm_head"], eps=arch["norm_eps"])


def logits(model: Dict[str, Any], params, tokens):
    """tokens [B, T] int32 -> float32 logits [B, T, vocabulary held]."""
    arch = arch_of(model)
    key = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_sequence(arch, key, params, jnp.asarray(row)) for row in tokens])


def loss(model: Dict[str, Any], params, tokens):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1]."""
    lg = logits(model, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
