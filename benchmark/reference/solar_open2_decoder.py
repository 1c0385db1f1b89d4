"""The plain reference of the Solar-Open2 block (``upstage/Solar-Open2-250B``
``config.json``, ``model_type: solar_open2``; the catalog describes it as
"gated delta-rule linear (neg. eigenvalues, conv4); softmax NoPE GQA 64Q/8KV
- 48L 3:1; 320 experts, top-8, 1 shared"): the forward pass and next-token
loss in straightforward ``jax.numpy`` and float32, matrix multiplications at
precision ``highest``. No kernel, no cache, no chunking, no sort: the delta
rule is a plain ``lax.scan`` over time, the experts a loop one expert at a
time. Nothing is imported from the program under test.

``x`` is the residual stream; pre-norm, two sub-blocks a layer:
``x += mixer(RMSNorm(x))``, ``x += moe(RMSNorm(x))`` (``rms_norm_eps``).
Layers 0, 4, 8, ... (``gqa_layers``) are softmax layers, the rest linear;
``first_k_dense_replace`` is 0, so every layer has the MoE.

Softmax layer (``use_rope`` false, ``use_gqa_gate`` true), ``h = RMSNorm(x)``:

    q, k, v = h Wq, h Wk, h Wv                 no bias, no positional term at all
    attn = softmax(causal(q k^T / sqrt(d))) v   NH query heads over NKV kv heads
    mixer = (attn * sigmoid(h Wg)) Wo

Linear layer (``linear_attn_config``: heads of ``d`` for q, k and v,
``short_conv_kernel_size`` 4, ``kda_allow_neg_eigval`` true):

    q~, k~, v~ = h Wq, h Wk, h Wv
    each through its own depthwise causal convolution of 4 taps, then SiLU
    per head: q = l2norm(q~) / sqrt(d), k = l2norm(k~), v = v~
    a_t = exp(-exp(A_log) * softplus(Wf_up (Wf_down h) + dt_bias))   in (0,1)^d, a key channel
    b_t = 2 sigmoid(h w_b)                      a head; the 2 is allow_neg_eigval
    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T      S [d x d], float32, S_0 = 0
    o_t = S_t^T q_t
    mixer = (RMSNorm_head(o_t) * sigmoid(Wg_up (Wg_down h))) Wo

MoE (``n_routed_experts``, ``num_experts_per_tok``, ``n_shared_experts`` 1,
``norm_topk_prob`` true, ``routed_scaling_factor`` 1), ``h = RMSNorm(x)``:

    s = sigmoid(h Wr)                           float32, over ALL routed experts
    top = the k largest of s + bias             the bias picks, it does not weigh
    w_e = s_e / sum of the k chosen s
    moe = sum_{e in top} w_e SwiGLU_e(h) + SwiGLU_shared(h)

then a final RMSNorm and the untied head.

ASSUMED (the config gives none of these; each is the convention of the
family whose keys the config uses, and is listed under ``assumed`` in the
configuration file too):

* the softmax layer's gate is element-wise, ``Wg [H, NH d]``, read from the
  normed layer input;
* the low ranks of the decay and of the linear layer's output gate (128); the
  float32 state; the head-wise RMSNorm's learned scale ``[d]``; ``l2norm`` as
  ``x / sqrt(sum x^2 + 1e-6)``; ``A_log`` a head, ``dt_bias`` a channel, and
  how the seeded weights draw them (``deepspeed_tpu/models/hybrid_moe.py``);
* sigmoid scoring with a selection-only bias (the config has no
  ``scoring_func``); ``intermediate_size`` 10240 is unused, there being no
  dense layer.

THE SHARE. The ``model`` section may hold one chip's share of a deployment
(``moe_expert_share = (index, of)``, ``num_experts`` held of
``moe_router_experts``): the router keeps its whole width and its k a token,
the weights are normalised over all k, and only the held experts' terms (plus
the shared expert) are summed: what that chip adds to the layer. The
vocabulary may be a slice; embedding and head are then that slice.

Same interface as every reference: ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``, and ``final_states(model, params, tokens)``
for a direct look at what the state store has to hold; weights in the program's own tree
(``periods/softmax/...``, ``periods/linear/...``, ``periods/moe/...``, leaves
``[periods, layers of that kind a period, ...]``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _conv_silu(x, w):
    """Depthwise causal convolution over time: ``x`` [B, T, C], ``w`` [K, C],
    ``w[K - 1]`` meeting the current token; zeros before the sequence."""
    K, T = w.shape[0], x.shape[1]
    ext = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[j] * ext[:, j : j + T] for j in range(K)))


def arch_of(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference needs of a configuration file's ``model``
    section; refuses a block this file does not describe."""
    kw = model["kwargs"]
    types = tuple(kw["layer_types"])
    index, of = kw.get("moe_expert_share", (0, 1))
    arch = {
        "layer_types": types,
        "num_heads": kw["num_heads"],
        "num_kv_heads": kw["num_kv_heads"],
        "head_dim": kw["head_dim"],
        "linear_heads": kw["linear_num_heads"],
        "linear_dim": kw["linear_head_dim"],
        "neg_eigval": bool(kw["linear_allow_neg_eigval"]),
        "norm_eps": kw["norm_eps"],
        "held": kw["num_experts"],
        "first_held": index * kw["num_experts"],
        "experts_per_token": kw["moe_top_k"],
        "routed_scaling": float(kw.get("moe_routed_scaling", 1.0)),
    }
    described = (
        len(types) == kw["num_layers"] and set(types) <= {"softmax", "linear"}
        and kw["norm"] == "rmsnorm" and kw["position"] == "none" and kw["activation"] == "swiglu"
        and kw["attn_output_gate"] is True and kw["linear_conv_kernel"] == 4 and not kw.get("use_bias", False)
        and not kw["tie_embeddings"] and kw["moe_scoring"] == "sigmoid" and kw["moe_select_bias"] is True
        and kw["moe_norm_topk_prob"] is True and kw["moe_shared_experts"] == 1 and kw.get("moe_drop_tokens") is False
        and kw["num_experts"] * of == kw["moe_router_experts"]
    )
    if not described:
        raise ValueError(f"the Solar-Open2 reference does not describe {kw}")
    return arch


def _period_of(types) -> int:
    L = len(types)
    return next(n for n in range(1, L + 1) if L % n == 0 and all(types[i] == types[i % n] for i in range(L)))


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _softmax_mixer(x, p, arch_key):
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    B, T, _ = x.shape
    N, NKV, D = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    q = (h @ p["wq"]).reshape(B, T, NKV, N // NKV, D)  # query head n reads kv head n // (N / NKV)
    k, v = (h @ p["wk"]).reshape(B, T, NKV, D), (h @ p["wv"]).reshape(B, T, NKV, D)
    scores = jnp.einsum("btkgd,bskd->bkgts", q, k) / jnp.sqrt(F32(D))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("bkgts,bskd->btkgd", probs, v).reshape(B, T, N * D)
    return x + (attn * jax.nn.sigmoid(h @ p["wg"])) @ p["wo"], None


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _linear_mixer(x, p, arch_key):
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    B, T, _ = x.shape
    N, D = arch["linear_heads"], arch["linear_dim"]
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    heads = lambda a: a.reshape(B, T, N, D)
    q = _l2(heads(_conv_silu(h @ p["wq"], p["conv_q"]))) / jnp.sqrt(F32(D))
    k = _l2(heads(_conv_silu(h @ p["wk"], p["conv_k"])))
    v = heads(_conv_silu(h @ p["wv"], p["conv_v"]))
    a = jnp.exp(-jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(heads((h @ p["wf_down"]) @ p["wf_up"] + p["dt_bias"])))
    b = jax.nn.sigmoid(h @ p["wb"]) * (2.0 if arch["neg_eigval"] else 1.0)  # [B, T, N]

    def step(S, t):
        q_t, k_t, v_t, a_t, b_t = t  # [B, N, D] x 4, [B, N]
        S = a_t[..., None] * S
        S = S - b_t[..., None, None] * k_t[..., None] * jnp.einsum("bnc,bncd->bnd", k_t, S)[..., None, :]
        S = S + b_t[..., None, None] * k_t[..., None] * v_t[..., None, :]
        return S, jnp.einsum("bncd,bnc->bnd", S, q_t)

    time_first = lambda t: jnp.moveaxis(t, 1, 0)
    S, o = jax.lax.scan(step, jnp.zeros((B, N, D, D), F32), tuple(time_first(t) for t in (q, k, v, a, b)))
    o = _rms(jnp.moveaxis(o, 0, 1), p["o_norm_scale"], arch["norm_eps"]).reshape(B, T, N * D)
    return x + (o * jax.nn.sigmoid((h @ p["wg_down"]) @ p["wg_up"])) @ p["wo"], S


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _router_and_shared(x, p, arch_key):
    """The second norm, each token's weight for each routed expert [B, T, E]
    (its normalised score where chosen, zero elsewhere) and the shared
    expert's output."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    h = _rms(x, p["mlp_norm_scale"], arch["norm_eps"])
    s = jax.nn.sigmoid(h @ p["gate"]["wg"])
    _, chosen = jax.lax.top_k(s + p["gate"]["bias"], arch["experts_per_token"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) * arch["routed_scaling"]
    weights = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32) * top[..., None], axis=-2)
    sh = p["shared"]
    return h, weights, (jax.nn.silu(h @ sh["w_gate"]) * (h @ sh["w_up"])) @ sh["w_out"]


@jax.jit
def _add_expert(acc, h, weight, w_gate, w_up, w_down):
    """acc + weight * expert(h), every token; one expert's matrices upcast."""
    return acc + weight[..., None] * ((jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) @ w_down.astype(F32))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, head, eps):
    return _rms(x, scale.astype(F32), eps) @ head.astype(F32)


def logits(model: Dict[str, Any], params, tokens):
    """tokens [B, T] int32 -> float32 logits [B, T, vocabulary held]."""
    return _forward(model, params, tokens)[0]


def final_states(model: Dict[str, Any], params, tokens):
    """The recurrent state ``S_T`` [B, heads, d, d] of every linear layer
    after the whole of ``tokens`` (every row the same length), in layer
    order: what a served row's entry of the state store has to hold."""
    return _forward(model, params, tokens)[1]


def _forward(model, params, tokens):
    arch = arch_of(model)
    key = tuple(sorted(arch.items()))
    n = _period_of(arch["layer_types"])
    periods = params["periods"]
    at = lambda tree, period, j: jax.tree_util.tree_map(lambda a: a[period, j], tree)
    states = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        for i, kind in enumerate(arch["layer_types"]):
            period, j = divmod(i, n)
            of_kind = arch["layer_types"][period * n : i].count(kind)  # which of the period's layers of this kind
            mixer = _softmax_mixer if kind == "softmax" else _linear_mixer
            x, state = mixer(x, at(periods[kind], period, of_kind), arch_key=key)
            if state is not None:
                states.append(state)
            moe = periods["moe"]
            h, weights, out = _router_and_shared(x, at({k: v for k, v in moe.items() if k != "experts"}, period, j), arch_key=key)
            for e in range(arch["held"]):  # the held experts' terms of the k-term sum
                w = (moe["experts"][name][period, j, e] for name in ("w_gate", "w_up", "w_out"))
                out = _add_expert(out, h, weights[..., arch["first_held"] + e], *w)
            x = x + out
        return _head(x, params["final_norm_scale"], params["lm_head"], eps=arch["norm_eps"]), states


def loss(model: Dict[str, Any], params, tokens):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1]."""
    lg = logits(model, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
