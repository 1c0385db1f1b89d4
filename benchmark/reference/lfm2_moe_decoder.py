"""The plain reference of the LFM2-MoE block (``LiquidAI/LFM2-24B-A2B``
``config.json``, ``model_type: lfm2_moe``; the catalog describes it as "gated
short conv; GQA - 40L: 30 conv + 10 attn; 64 experts, top-4, 0 shared"): the
forward pass and next-token loss in straightforward ``jax.numpy`` and float32,
matrix multiplications at precision ``highest``. No kernel, no cache, no
pages, no tail store, no chunks, no tiles, no sort: the convolution is a sum
of three shifted arrays over the whole sequence, attention a full causal
softmax, the experts a loop one held expert at a time over all tokens behind
a mask. Nothing is imported from the program under test.

``x`` is the residual stream; pre-norm, two sub-blocks a layer, no bias
anywhere (``conv_bias`` false):

    x <- x + mixer(RMSNorm(x; operator_norm))        the leaf attn_norm_scale
    x <- x + ffn(RMSNorm(x; ffn_norm))               the leaf mlp_norm_scale

then a final RMSNorm over the LAST hidden state (the family's
``embedding_norm``: it is not applied to the embedding) and the head, which is
the embedding table's transpose. ``layer_types[i]`` says what layer ``i``'s
mixer is; layers ``0 .. num_dense_layers - 1`` have a dense FFN, the rest the
routed one.

``conv``, the gated short convolution (``conv_L_cache`` = K = 3 taps), ``h =
RMSNorm(x)``, token ``t``:

    [B ; C ; x~] = h W_in          W_in [H, 3 H], the three parts of H in that order
    u_t = B_t * x~_t               a product a channel
    v_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t
                                   ONE depthwise causal convolution over the H channels of u, zeros
                                   before the sequence, NO bias, NO activation
    mixer = (C_t * v_t) W_out      W_out [H, H]

There is no SiLU, no recurrence and no decay: all a row carries from token to
token is ``u_{t-2}, u_{t-1}`` (the gated product, AFTER ``B *``).

``full_attention`` (the program's name: ``softmax``), token ``i`` at absolute
position ``i``:

    q = h Wq   NH heads of D        k = h Wk   NKV heads of D        v = h Wv   NKV heads of D
    q, k <- RMSNorm over each head's D features, one learned scale [D] for q and one for k,
            eps norm_eps, BEFORE the rotation
    q, k <- rotate-half over all D features (feature j pairs with j + D / 2, angle
            i * theta^(-j / (D / 2))), theta rope_theta (rope_type default)
    s_ij = q_i . k_j / sqrt(D), j <= i     query head n reads kv head n // (NH / NKV)
    mixer = concat_n(softmax(s) v) Wo      no gate, no window

The dense FFN (layers 0, 1): ``(silu(h Wg) * (h Wu)) Wd``. The routed FFN,
``h = RMSNorm(x)``:

    s = sigmoid(h Wr)                          float32, over ALL routed experts
    top = the k largest of s + expert_bias     the bias enters the CHOICE alone (use_expert_bias)
    w_e = s_e / (sum of the k chosen s + 1e-6) (norm_topk_prob), times routed_scaling_factor
    ffn = sum_{e in top} w_e SwiGLU_e(h)       no shared expert, no groups

DEPARTURES from the published description: none but the share below.

ASSUMED (each is listed under ``assumed`` in the configuration file too):
``head_dim`` = H / heads (the catalog row's is null); the head tied to the
table; float32 router logits and ``expert_bias``; the ``1e-6`` in the gates'
denominator (the family's modelling code; the PROGRAM divides by the bare sum,
``moe/routed_ffn.py::route``, a relative difference of ``1e-6 / sum`` < 1e-6 at
sums of 2-3.6).

THE SHARE. The ``model`` section may hold one chip's share of a deployment
(``moe_expert_share = (index, of)``, ``num_experts`` held of
``moe_router_experts``): the router keeps its whole width and its k a token,
the weights are normalised over all k, and only the held experts' terms are
summed: what that chip adds to the layer, and that partial result goes on to
the next layer.

Same interface as every reference: ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``; weights in the program's own tree
(``leading[i]/{mixer, ffn}``, ``periods/{softmax, conv, moe}/...`` with leaves
``[periods, layers of that kind a period, ...]``, ``trailing[i]/{mixer, moe}``
for the layers behind the last whole period: ``_layers`` walks them in the
model's own order). Computed a sequence at a time and a KV head's scores at a
time, so that ``[4, 1536]`` tokens fit beside a resident serving program.
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
    """``x`` [T, N, D], token ``i`` at position ``i``: every feature rotated, feature ``j`` with ``j + D / 2``."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * theta ** (-jnp.arange(half, dtype=F32) / half)  # [T, half]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


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
        "num_kv_heads": kw["num_kv_heads"],
        "head_dim": kw["head_dim"],
        "theta": float(kw["rope_theta"]),
        "taps": kw["conv_kernel"],
        "norm_eps": kw["norm_eps"],
        "held": kw["num_experts"],
        "first_held": index * kw["num_experts"],
        "experts_per_token": kw["moe_top_k"],
        "routed_scaling": float(kw.get("moe_routed_scaling", 1.0)),
    }
    described = (
        len(types) == kw["num_layers"] and set(types) <= {"softmax", "conv"} and 0 <= lead < len(types)
        and kw["norm"] == "rmsnorm" and kw["position"] == "rope" and kw["activation"] == "swiglu"
        and kw["qk_norm"] == "head" and not kw.get("rope_dim") and not kw.get("use_bias", False)
        and kw["tie_embeddings"] is True and kw["moe_scoring"] == "sigmoid" and kw["moe_select_bias"] is True
        and kw["moe_norm_topk_prob"] is True and kw.get("moe_shared_experts", 0) == 0 and kw.get("moe_drop_tokens") is False
        and kw["num_experts"] * of == kw["moe_router_experts"] and kw.get("v_head_dim", 0) in (0, kw["head_dim"])
        and kw.get("attn_softmax_scale") is None
    )
    if not described:
        raise ValueError(f"the LFM2-MoE reference does not describe {kw}")
    return arch


def _layers(arch, params):
    """``(kind, the mixer's leaves, "ffn" | "moe", that FFN's leaves)`` of every
    layer in the model's order, out of the program's tree: the leading layers'
    own leaves, then the stacks ``[periods, layers of a kind a period, ...]``
    walked period by period, then the trailing layers' own leaves (the layers
    behind the last WHOLE period of the shortest prefix that the list repeats
    whole, or at least twice whole and then in part)."""
    lead = arch["leading"]
    body = arch["layer_types"][lead:]
    repeats = lambda n: (len(body) % n == 0 or len(body) // n >= 2) and all(body[i] == body[i % n] for i in range(len(body)))
    n = next(n for n in range(1, len(body) + 1) if repeats(n))
    whole = len(body) // n * n
    at = lambda tree, *index: jax.tree_util.tree_map(lambda a: a[index], tree)
    for kind, p in zip(arch["layer_types"][:lead], params.get("leading", ())):
        yield kind, p["mixer"], "ffn", p["ffn"]
    for i, kind in enumerate(body[:whole]):
        period, j = divmod(i, n)
        of_kind = body[period * n : i].count(kind)  # which of the period's layers of this kind
        yield kind, at(params["periods"][kind], period, of_kind), "moe", at(params["periods"]["moe"], period, j)
    for kind, p in zip(body[whole:], params.get("trailing", ())):
        yield kind, p["mixer"], "moe", p["moe"]


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _conv_mixer(x, p, arch_key):
    """One sequence ``x`` [T, H] through a gated short convolution."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    T, H = x.shape
    K = arch["taps"]
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    bcx = h @ p["w_in"]
    B, C, xt = bcx[:, :H], bcx[:, H : 2 * H], bcx[:, 2 * H :]
    u = jnp.concatenate([jnp.zeros((K - 1, H), F32), B * xt])  # zeros before the sequence
    v = sum(p["conv_w"][j] * u[j : j + T] for j in range(K))  # tap K - 1 on the token itself
    return x + (C * v) @ p["wo"]


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _attention(x, p, arch_key):
    """One sequence ``x`` [T, H] through a full-attention layer's mixer."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    T = x.shape[0]
    N, NKV, D = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    q = _rotate(_rms((h @ p["wq"]).reshape(T, N, D), p["q_norm_scale"], arch["norm_eps"]), arch["theta"])
    k = _rotate(_rms((h @ p["wk"]).reshape(T, NKV, D), p["k_norm_scale"], arch["norm_eps"]), arch["theta"])
    v = (h @ p["wv"]).reshape(T, NKV, D)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    groups = q.reshape(T, NKV, N // NKV, D).transpose(1, 2, 0, 3)  # [NKV, G, T, D]: query head n reads kv head n // G

    def one_kv_head(args):
        qg, kh, vh = args  # [G, T, D], [T, D], [T, D]
        scores = jnp.where(seen, jnp.einsum("gtd,sd->gts", qg, kh) / jnp.sqrt(F32(D)), -jnp.inf)
        return jnp.einsum("gts,sd->gtd", jax.nn.softmax(scores, axis=-1), vh)

    attn = jax.lax.map(one_kv_head, (groups, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [NKV, G, T, D]
    return x + attn.transpose(2, 0, 1, 3).reshape(T, N * D) @ p["wo"]


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
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-6) * arch["routed_scaling"]
    return h, jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32) * top[..., None], axis=-2)


@jax.jit
def _add_expert(acc, h, weight, w_gate, w_up, w_down):
    """acc + weight * expert(h), every token; one expert's matrices upcast."""
    return acc + weight[..., None] * ((jax.nn.silu(h @ w_gate.astype(F32)) * (h @ w_up.astype(F32))) @ w_down.astype(F32))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, table, eps):
    return _rms(x, scale.astype(F32), eps) @ table.astype(F32).T


def routed_ffn(model: Dict[str, Any], p, x):
    """One routed FFN's addend for ``x`` [T, H] (what ``x`` gains: no residual),
    ``p`` one layer's leaves (``mlp_norm_scale``, ``gate``, ``experts`` [E held,
    ...]): the held experts' terms of each token's k-term sum."""
    arch = arch_of(model)
    with jax.default_matmul_precision("highest"):
        return _routed(arch, tuple(sorted(arch.items())), jnp.asarray(x, F32), p)


def _routed(arch, key, x, p):
    h, weights = _router(x, {k: v for k, v in p.items() if k != "experts"}, arch_key=key)
    out = jnp.zeros_like(x)
    for e in range(arch["held"]):
        w = (p["experts"][name][e] for name in ("w_gate", "w_up", "w_out"))
        out = _add_expert(out, h, weights[..., arch["first_held"] + e], *w)
    return out


def router_weights(model: Dict[str, Any], params, tokens):
    """Every routed layer's ``[T, router width]`` weights of ONE sequence ``tokens`` [T], in layer order."""
    arch = arch_of(model)
    with jax.default_matmul_precision("highest"):
        return _sequence(arch, tuple(sorted(arch.items())), params, jnp.asarray(tokens))[1]


def _sequence(arch, key, params, tokens):
    """One sequence ``tokens`` [T] -> (logits [T, V], the routed layers' router weights)."""
    x = params["embed"]["tokens"][tokens].astype(F32)
    routed = []
    for kind, mixer, ffn_kind, ffn in _layers(arch, params):
        x = (_conv_mixer if kind == "conv" else _attention)(x, mixer, arch_key=key)
        if ffn_kind == "ffn":
            x = _dense_ffn(x, ffn, eps=arch["norm_eps"])
        else:
            routed.append(_router(x, {k: v for k, v in ffn.items() if k != "experts"}, arch_key=key)[1])
            x = x + _routed(arch, key, x, ffn)
    return _head(x, params["final_norm_scale"], params["embed"]["tokens"], eps=arch["norm_eps"]), routed


def logits(model: Dict[str, Any], params, tokens):
    """tokens [B, T] int32 -> float32 logits [B, T, V]."""
    arch = arch_of(model)
    key = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_sequence(arch, key, params, jnp.asarray(row))[0] for row in tokens])


def loss(model: Dict[str, Any], params, tokens):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1]."""
    lg = logits(model, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
