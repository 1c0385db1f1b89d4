"""The plain reference of the MiMo-V2 language model's block
(``XiaomiMiMo/MiMo-V2.5`` ``config.json``, ``model_type: mimo_v2``; the
catalog describes it as "SWA(128) with learnable sink bias; global GQA - 48L,
5 SWA : 1 global; qk 192 / v 128; 256 experts, top-8, 0 shared"): the forward
pass and next-token loss in straightforward ``jax.numpy`` and float32, matrix
multiplications at precision ``highest``. No kernel, no cache, no pages, no
ring, no tiles, no sort: masks are built from positions, the sink is an
appended column, the experts a loop one expert at a time. Nothing is imported
from the program under test.

``x`` is the residual stream; pre-norm, two sub-blocks a layer:
``x += mixer(RMSNorm(x))``, ``x += ffn(RMSNorm(x))`` (``layernorm_epsilon``),
no biases, a final RMSNorm and the untied head. ``hybrid_layer_pattern[i]`` 0
is a FULL layer, 1 a WINDOW layer; ``moe_layer_freq[i]`` 0 is a dense FFN
(layer 0), 1 the routed one.

Both mixers, ``h = RMSNorm(x)``, token ``i`` at absolute position ``i``:

    q = h Wq   64 heads of 192        k = h Wk   NKV heads of 192
    v = h Wv   NKV heads of 128       NKV 4 in a full layer, 8 in a window layer
    q, k: the LEADING 64 features of every head rotated (rotate-half: feature j
          pairs with j + 32, angle i * theta^(-j / 32)), theta 1e7 full, 1e4 window;
          the other 128 features pass as they are
    v <- 0.707 v                                           (attention_value_scale)
    s_ij = q_i . k_j / sqrt(192)      query head n reads kv head n // (64 / NKV)
    full:    j <= i
    window:  i - 128 < j <= i, and one more column b_n (a learned scalar a head):
             P_ij = exp(s_ij - m_i) / (sum_j' exp(s_ij' - m_i) + exp(b_n - m_i))
             m_i the maximum over the row's live scores and b_n: the column has no
             value, so the row's weights sum to less than one
    mixer = concat_n(P v) Wo          Wo [64 x 128, 4096]; no QK norm, no output gate

Dense FFN (layer 0): ``(silu(h Wg) * (h Wu)) Wd``. Routed FFN (layers >= 1),
``h = RMSNorm(x)``:

    s = sigmoid(h Wr)                           float32, over ALL routed experts
    top = the k largest of s + bias             the bias picks, it does not weigh
    w_e = s_e / sum of the k chosen s           (norm_topk_prob; routed_scaling_factor null: 1)
    ffn = sum_{e in top} w_e SwiGLU_e(h)        no shared expert

ASSUMED (the config names the mechanism and not the convention; each is listed
under ``assumed`` in the configuration file too):

* ``attention_value_scale`` multiplies ``v`` before ``P v``;
* the window's edge: ``i - j < sliding_window``, the token itself included;
* the sink is a softmax column, one scalar a head, float32, dropped after;
* the softmax scale is ``192^-0.5`` (the query/key head's width);
* ``partial_rotary_factor`` 0.334 of 192 is ``int(64.128)`` = 64 features, the
  LEADING ones, rotate-half;
* the selection bias (``topk_method`` noaux_tc) is used for the choice alone;
  ``n_group`` = ``topk_group`` = 1 is no group limit;
* ``attention_chunk_size``, ``hybrid_block_size`` and
  ``attention_projection_layout: fused_qkv`` name a kernel hint and a storage
  layout and change no equation.

LEFT OUT, by name: the vision and audio towers (the configuration is the
language model's; the cell serves text ids) and the three
multi-token-prediction layers (not in the config; a served step yields one
token a row).

THE SHARE. The ``model`` section may hold one chip's share of a deployment
(``moe_expert_share = (index, of)``, ``num_experts`` held of
``moe_router_experts``): the router keeps its whole width and its k a token,
the weights are normalised over all k, and only the held experts' terms are
summed: what that chip adds to the layer. The vocabulary may be a slice;
embedding and head are then that slice.

Same interface as every reference: ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``; weights in the program's own tree
(``leading[i]/mixer``, ``leading[i]/ffn``, ``periods/softmax/...``,
``periods/window/...``, ``periods/moe/...``, the periods' leaves ``[periods,
layers of that kind a period, ...]``). Computed a sequence at a time, and a KV
head's scores at a time, so that ``[4, 2048]`` tokens fit beside a resident
serving program: a KV head's float32 scores are ``16 x 2048 x 2048 x 4 B`` =
268 MB.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate(x, theta, rope_dim):
    """``x`` [T, N, D], token ``i`` at position ``i``: the leading ``rope_dim``
    features rotated, feature ``j`` with ``j + rope_dim / 2``."""
    half = rope_dim // 2
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * theta ** (-jnp.arange(half, dtype=F32) / half)  # [T, half]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:rope_dim]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, x[..., rope_dim:]], axis=-1)


def arch_of(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference needs of a configuration file's ``model``
    section; refuses a block this file does not describe."""
    kw = model["kwargs"]
    types = tuple(kw["layer_types"])
    index, of = kw.get("moe_expert_share", (0, 1))
    lead = kw["leading_dense_layers"]
    arch = {
        "layer_types": types,
        "leading": lead,
        "num_heads": kw["num_heads"],
        "kv_heads": (("softmax", kw["num_kv_heads"]), ("window", kw["window_num_kv_heads"])),
        "theta": (("softmax", float(kw["rope_theta"])), ("window", float(kw["window_rope_theta"]))),
        "head_dim": kw["head_dim"],
        "v_head_dim": kw["v_head_dim"],
        "rope_dim": kw["rope_dim"],
        "window": kw["window"],
        "value_scale": float(kw["attn_value_scale"]),
        "norm_eps": kw["norm_eps"],
        "held": kw["num_experts"],
        "first_held": index * kw["num_experts"],
        "experts_per_token": kw["moe_top_k"],
        "routed_scaling": float(kw.get("moe_routed_scaling", 1.0)),
    }
    described = (
        len(types) == kw["num_layers"] and set(types) <= {"softmax", "window"} and 0 <= lead < len(types)
        and kw["norm"] == "rmsnorm" and kw["position"] == "rope" and kw["activation"] == "swiglu"
        and kw["window_sinks"] is True and not kw.get("attn_output_gate", False) and not kw.get("use_bias", False)
        and not kw["tie_embeddings"] and kw["moe_scoring"] == "sigmoid" and kw["moe_select_bias"] is True
        and kw["moe_norm_topk_prob"] is True and kw.get("moe_shared_experts", 0) == 0 and kw.get("moe_drop_tokens") is False
        and kw["num_experts"] * of == kw["moe_router_experts"]
    )
    if not described:
        raise ValueError(f"the MiMo-V2 reference does not describe {kw}")
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
    N, NKV, D, Dv = arch["num_heads"], dict(arch["kv_heads"])[kind], arch["head_dim"], arch["v_head_dim"]
    theta = dict(arch["theta"])[kind]
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    q = _rotate((h @ p["wq"]).reshape(T, N, D), theta, arch["rope_dim"])
    k = _rotate((h @ p["wk"]).reshape(T, NKV, D), theta, arch["rope_dim"])
    v = (h @ p["wv"]).reshape(T, NKV, Dv) * arch["value_scale"]
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = j <= i
    if kind == "window":
        seen &= i - j < arch["window"]
    # query head n reads kv head n // (N / NKV): a kv head's group at a time
    groups = q.reshape(T, NKV, N // NKV, D).transpose(1, 2, 0, 3)  # [NKV, G, T, D]
    sinks = p["sinks"].reshape(NKV, N // NKV) if kind == "window" else jnp.zeros((NKV, N // NKV), F32)

    def one_kv_head(args):
        qg, kh, vh, sink = args  # [G, T, D], [T, D], [T, Dv], [G]
        scores = jnp.where(seen, jnp.einsum("gtd,sd->gts", qg, kh) / jnp.sqrt(F32(D)), -jnp.inf)
        if kind == "window":  # one more column, with no value
            scores = jnp.concatenate([scores, jnp.broadcast_to(sink[:, None, None], scores.shape[:2] + (1,))], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)[..., :T]
        return jnp.einsum("gts,sd->gtd", probs, vh)

    attn = jax.lax.map(one_kv_head, (groups, k.transpose(1, 0, 2), v.transpose(1, 0, 2), sinks))  # [NKV, G, T, Dv]
    return x + attn.transpose(2, 0, 1, 3).reshape(T, N * Dv) @ p["wo"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, p, eps):
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    h = _rms(x, p["mlp_norm_scale"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_out"]


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _router(x, p, arch_key):
    """The second norm and each token's weight for each routed expert [T, E]
    (its normalised score where chosen, zero elsewhere)."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    h = _rms(x, p["mlp_norm_scale"], arch["norm_eps"])
    s = jax.nn.sigmoid(h @ p["gate"]["wg"])
    _, chosen = jax.lax.top_k(s + p["gate"]["bias"], arch["experts_per_token"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) * arch["routed_scaling"]
    return h, jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32) * top[..., None], axis=-2)


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
        h, weights = _router(x, at({k: v for k, v in moe.items() if k != "experts"}, period, j), arch_key=key)
        out = jnp.zeros_like(x)
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
