"""The plain reference of the Laguna language model's block
(``poolside/Laguna-S-2.1`` ``config.json``, ``model_type: laguna``; the catalog
describes it as "SWA(512) GQA 48Q/8KV; global 1 in 4 - 48L 3:1; 256 experts,
top-10, 1 shared; routed scaling 2.5"): the forward pass and next-token loss in
straightforward ``jax.numpy`` and float32, matrix multiplications at precision
``highest``. No kernel, no cache, no pages, no ring, no tiles, no sort: masks
are built from positions, the YaRN frequencies from the published numbers, the
experts a loop one expert at a time. Nothing is imported from the program under
test.

``x`` is the residual stream; pre-norm, two sub-blocks a layer:
``x += mixer(RMSNorm(x))``, ``x += ffn(RMSNorm(x))`` (``rms_norm_eps``), no
biases, a final RMSNorm and the untied head. ``layer_types[i]``
``full_attention`` is a FULL layer (``softmax`` here), ``sliding_attention`` a
WINDOW layer; ``mlp_layer_types[i]`` ``dense`` is a dense FFN (layer 0),
``sparse`` the routed one.

Both mixers, ``h = RMSNorm(x)``, token ``i`` at absolute position ``i``, ``N``
query heads (48 in a full layer, 72 in a window layer:
``num_attention_heads_per_layer``) over 8 KV heads of 128:

    q = h Wq   N heads of 128      k = h Wk   8 heads of 128      v = h Wv   8 heads of 128
    full:    the LEADING 64 features of every q and k head rotated (rotate-half:
             feature n pairs with n + 32, angle i * f'_n), the other 64 pass as they are;
             f_n = theta^(-n / 32), theta 5e5;  f'_n = (1 - r_n) f_n / 128 + r_n f_n,
             r_n = 1 - clip((n - 9) / (18 - 9), 0, 1), where 9 = floor and 18 = ceil of
             64 ln(8192 / (2 pi b)) / (2 ln 5e5) at b = beta_fast 32, beta_slow 1;
             cos and sin times attention_factor 1.4852 (= 0.1 ln 128 + 1)
    window:  all 128 features rotated, feature n with n + 64, angle i * 1e4^(-n / 64)
    s_ij = q_i . k_j / sqrt(128)    query head n reads kv head n // (N / 8)
    full:    j <= i                 window:  i - 512 < j <= i
    o_n = softmax_j(s) v                                                      [128]
    gate_n = sigmoid(h w_gate,n)    one scalar a head, W_gate [3072, N]
    mixer = concat_n(gate_n o_n) Wo          Wo [N x 128, 3072]; no QK norm

Dense FFN (layer 0): ``(silu(h Wg) * (h Wu)) Wd``. Routed FFN (layers >= 1),
``h = RMSNorm(x)``:

    p = softmax(h Wr)                           float32, over ALL routed experts
    top = the 10 largest of p
    w_e = 2.5 * p_e / sum of the 10 chosen p    (norm_topk_prob; moe_routed_scaling_factor)
    ffn = sum_{e in top} w_e SwiGLU_e(h) + SwiGLU_shared(h)

ASSUMED (the config names the mechanism and not the convention; each is listed
under ``assumed`` in the configuration file too, with what a checkpoint would
settle):

* ``gating: per-head`` is one sigmoid scalar a head, from the layer's normed
  input, multiplying that head's attention output before ``Wo`` (the head-wise
  variant of arXiv:2505.06708);
* the router scores by softmax over all experts (the MoE keys are the Qwen-MoE
  family's; no ``scoring_func``, ``topk_method``, group or bias key);
  ``moe_router_logit_softcapping`` 0 is off; ``moe_apply_router_weight_on_input``
  false: the weight multiplies the expert's output;
* the shared expert is added plain (no key names a gate on it);
* no QK norm (no key names one);
* rotate-half pairing, and ``partial_rotary_factor`` 0.5 rotates the LEADING
  half of a head;
* the window's edge: ``i - j < sliding_window``, the token itself included;
* the YaRN ramp is computed over the rotated 64 features, not over 128.

LEFT OUT: nothing the config describes.

THE SHARE. The ``model`` section may hold one chip's share of a deployment
(``moe_expert_share = (index, of)``, ``num_experts`` held of
``moe_router_experts``): the router keeps its whole width and its k a token,
the weights are normalised over all k, and only the held experts' terms are
summed, plus the shared expert, which every chip computes alike: what that chip
adds to the layer. The vocabulary may be a slice; embedding and head are then
that slice.

Same interface as every reference: ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``; weights in the program's own tree
(``leading[i]/mixer``, ``leading[i]/ffn``, ``periods/softmax/...``,
``periods/window/...``, ``periods/moe/...``, the periods' leaves ``[periods,
layers of that kind a period, ...]``). Computed a sequence at a time, a layer's
weights at a time and a KV head's scores at a time, so that ``[4, 2048]`` tokens
fit beside a resident serving program: a KV head's float32 scores are ``9 x 2048
x 2048 x 4 B`` = 151 MB.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def yarn_frequencies(width: int, theta: float, factor: float, original_positions: int, beta_fast: float, beta_slow: float):
    """The ``width / 2`` angular frequencies of a YaRN-scaled rotary term, from
    the published numbers: pair ``n`` keeps ``theta^(-2n / width)`` up to the
    pair that turns ``beta_fast`` times within the original positions, has it
    divided by ``factor`` from the pair that turns ``beta_slow`` times on, and
    is blended linearly between."""
    half = width // 2
    turns_at = lambda beta: width * math.log(original_positions / (2 * math.pi * beta)) / (2 * math.log(theta))
    low, high = max(math.floor(turns_at(beta_fast)), 0), min(math.ceil(turns_at(beta_slow)), width - 1)
    n = np.arange(half, dtype=np.float64)
    plain = theta ** (-n / half)
    kept = 1.0 - np.clip((n - low) / max(high - low, 1e-3), 0.0, 1.0)  # r_n
    return (1.0 - kept) * plain / factor + kept * plain


def _rotate(x, freqs, factor):
    """``x`` [T, N, D], token ``i`` at position ``i``: the leading ``2
    len(freqs)`` features rotated, feature ``n`` with ``n + len(freqs)``, cos
    and sin times ``factor``."""
    half = len(freqs)
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(freqs, F32)  # [T, half]
    cos, sin = factor * jnp.cos(angle)[:, None, :], factor * jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half : 2 * half]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, x[..., 2 * half :]], axis=-1)


def arch_of(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference needs of a configuration file's ``model``
    section; refuses a block this file does not describe."""
    kw = model["kwargs"]
    types = tuple(kw["layer_types"])
    index, of = kw.get("moe_expert_share", (0, 1))
    lead = kw["leading_dense_layers"]
    yarn = (float(kw["rope_yarn_factor"]), int(kw["rope_yarn_original_positions"]), float(kw["rope_yarn_beta_fast"]), float(kw["rope_yarn_beta_slow"]))
    arch = {
        "layer_types": types,
        "leading": lead,
        "heads": (("softmax", kw["num_heads"]), ("window", kw["window_num_heads"])),
        "kv_heads": kw["num_kv_heads"],
        "theta": (("softmax", float(kw["rope_theta"])), ("window", float(kw["window_rope_theta"]))),
        "rope_dim": (("softmax", kw["rope_dim"]), ("window", kw["window_rope_dim"])),
        "yarn": yarn,
        "attention_factor": float(kw.get("rope_yarn_attention_factor") or 0.1 * math.log(yarn[0]) + 1.0),
        "head_dim": kw["head_dim"],
        "window": kw["window"],
        "norm_eps": kw["norm_eps"],
        "held": kw["num_experts"],
        "first_held": index * kw["num_experts"],
        "experts_per_token": kw["moe_top_k"],
        "routed_scaling": float(kw["moe_routed_scaling"]),
    }
    described = (
        len(types) == kw["num_layers"] and set(types) <= {"softmax", "window"} and 0 <= lead < len(types)
        and kw["norm"] == "rmsnorm" and kw["position"] == "rope" and kw["activation"] == "swiglu"
        and kw["attn_head_gate"] is True and not kw.get("attn_output_gate", False) and not kw.get("window_sinks", False)
        and not kw.get("use_bias", False) and not kw["tie_embeddings"] and kw.get("v_head_dim", 0) in (0, kw["head_dim"])
        and kw.get("attn_value_scale", 1.0) == 1.0 and kw.get("window_num_kv_heads", 0) in (0, kw["num_kv_heads"])
        and kw["moe_scoring"] == "softmax" and not kw.get("moe_select_bias", False) and kw["moe_norm_topk_prob"] is True
        and kw["moe_shared_experts"] == 1 and kw.get("moe_drop_tokens") is False
        and kw["num_experts"] * of == kw["moe_router_experts"]
    )
    if not described:
        raise ValueError(f"the Laguna reference does not describe {kw}")
    return arch


def _period_of(types) -> int:
    L = len(types)
    return next(n for n in range(1, L + 1) if L % n == 0 and all(types[i] == types[i % n] for i in range(L)))


@functools.partial(jax.jit, static_argnames=("arch_key", "kind"))
def _mixer(x, p, arch_key, kind):
    """One sequence ``x`` [T, H] through a full (``softmax``) or a window layer's mixer."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    T = x.shape[0]
    N, NKV, D = dict(arch["heads"])[kind], arch["kv_heads"], arch["head_dim"]
    theta, width = dict(arch["theta"])[kind], dict(arch["rope_dim"])[kind]
    if kind == "softmax":
        freqs, factor = yarn_frequencies(width, theta, *arch["yarn"]), arch["attention_factor"]
    else:
        freqs, factor = theta ** (-np.arange(width // 2, dtype=np.float64) / (width // 2)), 1.0
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    q = _rotate((h @ p["wq"]).reshape(T, N, D), freqs, factor)
    k = _rotate((h @ p["wk"]).reshape(T, NKV, D), freqs, factor)
    v = (h @ p["wv"]).reshape(T, NKV, D)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if kind == "window":
        seen &= i - j < arch["window"]
    # query head n reads kv head n // (N / NKV): a kv head's group at a time
    groups = q.reshape(T, NKV, N // NKV, D).transpose(1, 2, 0, 3)  # [NKV, G, T, D]

    def one_kv_head(args):
        qg, kh, vh = args  # [G, T, D], [T, D], [T, D]
        scores = jnp.where(seen, jnp.einsum("gtd,sd->gts", qg, kh) / jnp.sqrt(F32(D)), -jnp.inf)
        return jnp.einsum("gts,sd->gtd", jax.nn.softmax(scores, axis=-1), vh)

    attn = jax.lax.map(one_kv_head, (groups, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [NKV, G, T, D]
    gate = jax.nn.sigmoid(h @ p["wg_head"])  # [T, N]: one scalar a head
    gated = attn.transpose(2, 0, 1, 3).reshape(T, N, D) * gate[..., None]
    return x + gated.reshape(T, N * D) @ p["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, p, eps):
    """``x + SwiGLU(RMSNorm(x))``: the leading layer's FFN."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    h = _rms(x, p["mlp_norm_scale"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_out"]


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _router(x, p, arch_key):
    """The second norm, each token's weight for each routed expert [T, E] (2.5
    times its normalised probability where chosen, zero elsewhere) and the
    shared expert's term."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    h = _rms(x, p["mlp_norm_scale"], arch["norm_eps"])
    probs = jax.nn.softmax(h @ p["gate"]["wg"], axis=-1)
    top, chosen = jax.lax.top_k(probs, arch["experts_per_token"])
    top = top / jnp.sum(top, axis=-1, keepdims=True) * arch["routed_scaling"]
    shared = (jax.nn.silu(h @ p["shared"]["w_gate"]) * (h @ p["shared"]["w_up"])) @ p["shared"]["w_out"]
    return h, jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1], dtype=F32) * top[..., None], axis=-2), shared


@jax.jit
def _add_expert(acc, h, weight, w_gate, w_up, w_down):
    """acc + weight * expert(h), every token; one expert's matrices upcast."""
    return acc + weight[..., None] * ((jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) @ w_down.astype(F32))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, head, eps):
    return _rms(x, scale.astype(F32), eps) @ head.astype(F32)


def _sequence(arch, key, params, tokens):
    """One sequence ``tokens`` [T] -> logits [T, V]."""
    lead = arch["leading"]
    body = arch["layer_types"][lead:]
    n = _period_of(body)
    periods = params["periods"]
    at = lambda tree, period, j: jax.tree_util.tree_map(lambda a: a[period, j], tree)
    x = params["embed"]["tokens"][tokens].astype(F32)
    for kind, p in zip(arch["layer_types"][:lead], params.get("leading", ())):
        x = _mixer(x, p["mixer"], arch_key=key, kind=kind)
        x = _dense_ffn(x, p["ffn"], eps=arch["norm_eps"])
    for i, kind in enumerate(body):
        period, j = divmod(i, n)
        of_kind = body[period * n : i].count(kind)  # which of the period's layers of this kind
        x = _mixer(x, at(periods[kind], period, of_kind), arch_key=key, kind=kind)
        moe = periods["moe"]
        h, weights, out = _router(x, at({k: v for k, v in moe.items() if k != "experts"}, period, j), arch_key=key)
        for e in range(arch["held"]):  # the held experts' terms of the k-term sum
            w = (moe["experts"][name][period, j, e] for name in ("w_gate", "w_up", "w_out"))
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
