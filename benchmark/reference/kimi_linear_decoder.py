"""The plain reference of Kimi-Linear's block
(``moonshotai/Kimi-Linear-48B-A3B-Instruct`` ``config.json``, ``model_type:
kimi_linear``; the catalog describes it as "KDA gated delta-rule linear
(conv4); MLA NoPE global - 27L, 3 KDA : 1 MLA; 256 experts, top-8, 1 shared"):
the forward pass and next-token loss in straightforward ``jax.numpy`` and
float32, matrix multiplications at precision ``highest``. No kernel, no cache,
no pages, no chunking, no absorbed product, no tiles, no sort: the delta rule
is a plain ``lax.scan`` over time, token by token; the latent layer is the
PUBLISHED (expanded) form, every head's keys and values made from the latents
of the whole sequence; the experts are a loop one expert at a time; a layer's
weights are upcast a layer at a time. Nothing is imported from the program
under test.

``x`` is the residual stream; pre-norm, two sub-blocks a layer:
``x += mixer(RMSNorm(x))``, ``x += ffn(RMSNorm(x))`` (``rms_norm_eps`` 1e-5), no
biases, a final RMSNorm and the untied head. The first ``first_k_dense_replace``
layers (published layer 1, a KDA layer) have a dense FFN, the others the
routed one. Published layers 4, 8, ..., 24 and 27 are latent layers
(``full_attn_layers``, counted from 1), the rest KDA layers (``kda_layers``).

KDA layer (``linear_attn_config``: 32 heads of ``d`` = 128 for q, k and v,
``short_conv_kernel_size`` 4), ``h = RMSNorm(x)``:

    q~, k~, v~ = h Wq, h Wk, h Wv                            [2304, 32 x 128] each
    each through its own depthwise causal convolution of 4 taps, then SiLU
    per head: q = l2norm(q~) / sqrt(d), k = l2norm(k~), v = v~
    a_t = exp(-exp(A_log) * softplus(Wf_up (Wf_down h) + dt_bias))   in (0,1)^d, a KEY CHANNEL
    b_t = sigmoid(h w_b)                       a head; ONCE: the config has no allow_neg_eigval
    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T      S [d x d], float32, S_0 = 0
    o_t = S_t^T q_t
    mixer = (RMSNorm_head(o_t) * sigmoid(Wg_up (Wg_down h))) Wo

Latent layer (``mla_use_nope`` true, ``q_lora_rank`` null), token ``i``, head
``n`` of 32:

    [q_nope_n (128) ; q_r_n (64)] = h Wq,n       NO low rank, NO query norm
    [c ; r] = h Wkv_a                            [512 + 64]
    c_kv = RMSNorm(c)     the 512 alone          k_r = r   UNROTATED, ONE for all heads
    k_nope_n = c_kv Wk_b,n   [128]               v_n = c_kv Wv_b,n   [128]
    s_n(i, j) = (q_nope_n,i . k_nope_n,j + q_r_n,i . k_r,j) / sqrt(192),   j <= i
    o_n,i = sum_j softmax_j(s_n(i, .)) v_n,j     mixer = concat_n(o_n) Wo      Wo [32 x 128, 2304]

    No positional term of any kind: ``rope_theta`` is unused.

Dense FFN (layer 1): ``(silu(h Wg) * (h Wu)) Wd``, width 9,216. Routed FFN
(layers >= 2), ``h = RMSNorm(x)``:

    s = sigmoid(h Wr)                           float32, over ALL 256 routed experts
    top = the 8 largest of s + bias             the bias picks, it does not weigh
    w_e = 2.446 s_e / sum of the 8 chosen s     (moe_renormalize, routed_scaling_factor)
    ffn = sum_{e in top} w_e SwiGLU_e(h) + SwiGLU_shared(h)     widths 1,024

DEPARTURES from the published description and ASSUMED sizes, each also under
``assumed`` in the configuration file:

* the published ``kv_b_proj`` maps ``c_kv`` to ``[k_nope_n ; v_n]`` a head; the
  program's tree stores its two parts apart (``wk_b`` [512, 32 x 128], ``wv_b``
  [512, 32 x 128]) and this file reads them so: the same products;
* ``mla_use_nope``: the 64 features beside the latent are kept (the tensors
  have them: ``qk_rope_head_dim`` 64) and are not rotated, in q or in k; the
  softmax scale is ``192^-0.5`` with no ``mscale`` (``rope_scaling`` null);
* the low ranks of the decay and of the KDA output gate (128: the config gives
  none; the family's ``head_dim``); the float32 state; the head-wise RMSNorm's
  learned scale ``[d]``; ``l2norm`` as ``x / sqrt(sum x^2 + 1e-6)``; ``A_log`` a
  head, ``dt_bias`` a channel, and how the seeded weights draw them
  (``deepspeed_tpu/models/hybrid_moe.py``);
* the router in float32, the selection bias for the choice alone,
  ``num_expert_group`` = ``topk_group`` = 1: no group limit;
* top-level ``head_dim`` 72 (= 2304 / 32) and ``num_key_value_heads`` 32 name no
  tensor of either mixer and are unused.

LEFT OUT: nothing (``num_nextn_predict_layers`` 0).

THE SHARE. The ``model`` section may hold one chip's share of a deployment
(``moe_expert_share = (index, of)``, ``num_experts`` held of
``moe_router_experts``): the router keeps its whole width and its 8 a token,
the weights are normalised over all 8, and only the held experts' terms are
summed, with no stand-in for the absent ones; the shared expert is whole. The
vocabulary may be a slice; embedding and head are then that slice.

Same interface as every reference: ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``, and ``final_states(model, params, tokens)``
for a direct look at what the state store has to hold; weights in the
program's own tree (``leading[i]/mixer``, ``leading[i]/ffn``,
``periods/linear/...``, ``periods/latent/...``, ``periods/moe/...``, the
periods' leaves ``[periods, layers of that kind a period, ...]``). Computed a
sequence at a time and a latent head's scores at a time, so that ``[4, 2048]``
tokens fit beside a resident serving program.
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
    """Depthwise causal convolution over time: ``x`` [T, C], ``w`` [K, C],
    ``w[K - 1]`` meeting the current token; zeros before the sequence."""
    K, T = w.shape[0], x.shape[0]
    ext = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(w[j] * ext[j : j + T] for j in range(K)))


def arch_of(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference needs of a configuration file's ``model``
    section; refuses a block this file does not describe."""
    kw = model["kwargs"]
    types = tuple(kw["layer_types"])
    index, of = kw.get("moe_expert_share", (0, 1))
    arch = {
        "layer_types": types,
        "leading": kw["leading_dense_layers"],
        "num_heads": kw["num_heads"],
        "nope": kw["qk_nope_head_dim"],
        "shared": kw["qk_rope_head_dim"],
        "v_head_dim": kw["v_head_dim"],
        "kv_lora_rank": kw["kv_lora_rank"],
        "linear_heads": kw["linear_num_heads"],
        "linear_dim": kw["linear_head_dim"],
        "norm_eps": kw["norm_eps"],
        "held": kw["num_experts"],
        "first_held": index * kw["num_experts"],
        "experts_per_token": kw["moe_top_k"],
        "routed_scaling": float(kw["moe_routed_scaling"]),
    }
    described = (
        len(types) == kw["num_layers"] and set(types) <= {"linear", "latent"} and 0 <= arch["leading"] < len(types)
        and kw["head_dim"] == arch["nope"] + arch["shared"] and kw.get("attn_softmax_scale") is None
        and not kw.get("q_lora_rank") and kw["norm"] == "rmsnorm" and kw["position"] == "none"
        and kw["activation"] == "swiglu" and kw["linear_conv_kernel"] == 4 and kw["linear_allow_neg_eigval"] is False
        and not kw.get("use_bias", False) and not kw["tie_embeddings"] and kw["moe_scoring"] == "sigmoid"
        and kw["moe_select_bias"] is True and kw["moe_norm_topk_prob"] is True and kw["moe_shared_experts"] == 1
        and kw.get("moe_drop_tokens") is False and kw["num_experts"] * of == kw["moe_router_experts"]
    )
    if not described:
        raise ValueError(f"the Kimi-Linear reference does not describe {kw}")
    return arch


def _period_of(types) -> int:
    L = len(types)
    return next(n for n in range(1, L + 1) if L % n == 0 and all(types[i] == types[i % n] for i in range(L)))


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _linear_mixer(x, p, arch_key):
    """One sequence ``x`` [T, H] through a KDA layer's mixer, token by token.
    Returns ``(x, S_T [heads, d, d])``."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    T = x.shape[0]
    N, D = arch["linear_heads"], arch["linear_dim"]
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    heads = lambda a: a.reshape(T, N, D)
    q = _l2(heads(_conv_silu(h @ p["wq"], p["conv_q"]))) / jnp.sqrt(F32(D))
    k = _l2(heads(_conv_silu(h @ p["wk"], p["conv_k"])))
    v = heads(_conv_silu(h @ p["wv"], p["conv_v"]))
    # the decay: a rate a head, a step a KEY CHANNEL
    a = jnp.exp(-jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(heads((h @ p["wf_down"]) @ p["wf_up"] + p["dt_bias"])))
    b = jax.nn.sigmoid(h @ p["wb"])  # [T, N]: once

    def step(S, t):
        q_t, k_t, v_t, a_t, b_t = t  # [N, D] x 4, [N]
        S = a_t[..., None] * S
        S = S - b_t[..., None, None] * k_t[..., None] * jnp.einsum("nc,ncd->nd", k_t, S)[..., None, :]
        S = S + b_t[..., None, None] * k_t[..., None] * v_t[..., None, :]
        return S, jnp.einsum("ncd,nc->nd", S, q_t)

    S, o = jax.lax.scan(step, jnp.zeros((N, D, D), F32), (q, k, v, a, b))
    o = _rms(o, p["o_norm_scale"], arch["norm_eps"]).reshape(T, N * D)
    return x + (o * jax.nn.sigmoid((h @ p["wg_down"]) @ p["wg_up"])) @ p["wo"], S


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _latent_mixer(x, p, arch_key):
    """One sequence ``x`` [T, H] through a latent layer's mixer, expanded, nothing rotated."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    T = x.shape[0]
    N, nope, shared, Dv, C = arch["num_heads"], arch["nope"], arch["shared"], arch["v_head_dim"], arch["kv_lora_rank"]
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    q = (h @ p["wq"]).reshape(T, N, nope + shared)  # no low rank, no query norm
    q_nope, q_r = q[..., :nope], q[..., nope:]
    kv = h @ p["wkv_a"]
    c_kv = _rms(kv[:, :C], p["kv_norm_scale"], arch["norm_eps"])  # the norm over the latent alone
    k_r = kv[:, C:]  # [T, shared]: one for all heads, unrotated
    k_nope = (c_kv @ p["wk_b"]).reshape(T, N, nope)
    v = (c_kv @ p["wv_b"]).reshape(T, N, Dv)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def one_head(args):
        qn, qr, kn, vh = args  # [T, nope], [T, shared], [T, nope], [T, Dv]
        scores = (qn @ kn.T + qr @ k_r.T) / jnp.sqrt(F32(nope + shared))
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ vh

    heads = lambda a: a.transpose(1, 0, 2)
    attn = jax.lax.map(one_head, (heads(q_nope), heads(q_r), heads(k_nope), heads(v)))  # [N, T, Dv]
    return x + attn.transpose(1, 0, 2).reshape(T, N * Dv) @ p["wo"], None


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, p, eps):
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    h = _rms(x, p["mlp_norm_scale"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_out"]


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _router(x, p, arch_key):
    """The second norm, each token's weight for each routed expert [T, E]
    (2.446 times its normalised score where chosen, zero elsewhere) and the
    shared expert's output."""
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


_MIXERS = {"linear": _linear_mixer, "latent": _latent_mixer}


def _sequence(arch, key, params, tokens):
    """One sequence ``tokens`` [T] -> (logits [T, V], the KDA layers' final states in layer order)."""
    types, lead = arch["layer_types"], arch["leading"]
    n = _period_of(types[lead:])
    periods = params["periods"]
    at = lambda tree, period, j: jax.tree_util.tree_map(lambda a: a[period, j], tree)
    states = []
    x = params["embed"]["tokens"][tokens].astype(F32)
    for kind, p in zip(types[:lead], params.get("leading", ())):
        x, state = _MIXERS[kind](x, p["mixer"], arch_key=key)
        states.append(state)
        x = _dense_ffn(x, p["ffn"], eps=arch["norm_eps"])
    for i, kind in enumerate(types[lead:]):
        period, j = divmod(i, n)
        of_kind = types[lead + period * n : lead + i].count(kind)  # which of the period's layers of this kind
        x, state = _MIXERS[kind](x, at(periods[kind], period, of_kind), arch_key=key)
        states.append(state)
        moe = periods["moe"]
        h, weights, out = _router(x, at({k: v for k, v in moe.items() if k != "experts"}, period, j), arch_key=key)
        for e in range(arch["held"]):  # the held experts' terms of the 8-term sum
            w = (moe["experts"][name][period, j, e] for name in ("w_gate", "w_up", "w_out"))
            out = _add_expert(out, h, weights[..., arch["first_held"] + e], *w)
        x = x + out
    return _head(x, params["final_norm_scale"], params["lm_head"], eps=arch["norm_eps"]), [s for s in states if s is not None]


def _forward(model, params, tokens):
    arch = arch_of(model)
    key = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        rows = [_sequence(arch, key, params, jnp.asarray(row)) for row in tokens]
    return jnp.stack([lg for lg, _ in rows]), [jnp.stack(layer) for layer in zip(*(states for _, states in rows))]


def logits(model: Dict[str, Any], params, tokens):
    """tokens [B, T] int32 -> float32 logits [B, T, vocabulary held]."""
    return _forward(model, params, tokens)[0]


def final_states(model: Dict[str, Any], params, tokens):
    """The recurrent state ``S_T`` [B, heads, d, d] of every KDA layer after
    the whole of ``tokens`` (every row the same length), in layer order, the
    leading layer's first: what a served row's entries of the state store
    have to hold."""
    return _forward(model, params, tokens)[1]


def loss(model: Dict[str, Any], params, tokens):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1]."""
    lg = logits(model, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
