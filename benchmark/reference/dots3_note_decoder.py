"""The plain reference of dots3-note-prev's language block
(``dots-studio/dots3-note-prev`` ``config.json``, ``model_type: dots3_note``;
the catalog describes it as "MLA + DSA indexer (full layers); SWA(513) with its
own low-rank (kv_lora 1024) latent attention + headwise gate; 256 experts,
top-8, 1 shared"): the forward pass and next-token loss in straightforward
``jax.numpy`` and float32, matrix multiplications at precision ``highest``, in
the PUBLISHED (expanded) form: every head's keys and values are made from the
latents of the whole sequence, the selection is a stable ``argsort`` of the
indexer's scores a query. No kernel, no cache, no pages, no ring, no absorbed
product, no tiles, no top-k primitive: masks are built from positions and
ranks, the experts are a loop one expert at a time, a layer's weights are
upcast a layer at a time. Nothing is imported from the program under test.

``x`` is the residual stream; pre-norm, two sub-blocks a layer: ``x +=
mixer(RMSNorm(x))``, ``x += ffn(RMSNorm(x))`` (``rms_norm_eps`` 1e-5), no
biases but the indexer's LayerNorm's, a final RMSNorm and the untied head. The
first ``first_k_dense_replace`` layers have a dense FFN, the others the routed
one. ``h = RMSNorm(x)``, token ``i`` at absolute position ``i``.

A FULL layer (``full_attention``: ``sparse_latent`` in the program's
``layer_types``), head ``n`` of 128:

    c_q = s_q RMSNorm(h Wq_a)                    [1024]   s_q  = (5120 / 1024)^0.5
    [q_nope_n (128) ; q_rope_n (64)] = c_q Wq_b,n
    [c ; r] = h Wkv_a                            [512 + 64]
    c_kv = s_kv RMSNorm(c)   the 512 alone       s_kv = (5120 / 512)^0.5
    k_rope = RoPE(r, i)      ONE, shared by all heads
    k_nope_n = c_kv Wk_b,n   [128]               v_n = c_kv Wv_b,n   [128]

    the indexer, head ``j`` of 64:
    qI_j = RoPE64(c_q WI_qb,j)   [128]           kI = RoPE64(LayerNorm(h WI_k))   [128], scale and bias
    w = (h WI_w) 64^-0.5 128^-0.5                [64]
    I(i, s) = sum_j w_j(i) ReLU(qI_j(i) . kI(s))           float32
    S_i = the 2,048 keys s <= i of largest I(i, s); all of them while i < 2,048; ties to the lower position

    s_n(i, s) = (q_nope_n,i . k_nope_n,s + RoPE(q_rope_n,i, i) . k_rope_s) / sqrt(192),   s in S_i
    o_n,i = sum_{s in S_i} softmax_{S_i}(s_n(i, .)) v_n,s
    g = sigmoid(h Wg)   [128]                    mixer = concat_n(g_n o_n) Wo

A WINDOW layer (``sliding_attention``: ``window_latent``), head ``n`` of 64: the
same latent attention at its own widths (q low rank 1,024, latent 1,024, a
head's query and key 192 + 64, value 128, theta 5e4, both rescales (5120 /
1024)^0.5, scale 256^-0.5), over the keys ``i - 513 < s <= i``, no indexer,
a gate of 64.

    RoPE: rotate-half inside the rotated width (feature j pairs with j + 32,
    angle i * theta^(-j / 32)); RoPE64 rotates the LEADING 64 of the
    indexer's 128 and passes the rest.

Dense FFN (layer 0): ``(silu(h Wg) * (h Wu)) Wd``, width 13,824. Routed FFN
(layers >= 1), ``h = RMSNorm(x)``:

    s = sigmoid(h Wr)                           float32, over ALL 256 routed experts
    top = the 8 largest of s + bias             the bias picks, it does not weigh
    w_e = s_e / sum of the 8 chosen s           (norm_topk_prob; routed_scaling_factor 1)
    ffn = sum_{e in top} w_e SwiGLU_e(h) + SwiGLU_shared(h)     widths 1,536

DEPARTURES from the published description and INFERENCES from the catalog row
(its ``config`` names the widths, ``described_as`` the mechanisms; no modelling
code is at hand), each also under ``assumed`` in the configuration file and
marked ``# assumed`` at its line below:

* ``apply_mla_qkv_lora_rescale``: both normed low ranks times (hidden_size /
  rank)^0.5, the form LongCat-Flash publishes as ``mla_scale_q_lora`` /
  ``mla_scale_kv_lora``;
* the indexer is DeepSeek-V3.2's published one (``index_n_heads``,
  ``index_head_dim``, ``index_topk`` are its keys): queries from the query's
  low rank, one key a token through a LayerNorm with a bias (eps
  ``rms_norm_eps``), a weight a head from the hidden state, the leading
  ``qk_rope_head_dim`` features of both rotated with the layer's theta;
* ``sliding_window_size`` 513 counts the query's own position;
* the published ``kv_b_proj`` is stored as its two parts (``wk_b``, ``wv_b``);
  rotate-half pairing; scales (nope + rope)^-0.5 with no mscale
  (``rope_scaling`` null); the router float32, the selection bias for the
  choice alone.

LEFT OUT, by name: the vision and audio towers (the catalog row's ``config``
is the language model's) and the multi-token-prediction layer (a serving
recipe's, not in ``config``).

THE SHARE. The ``model`` section may hold one chip's share of a deployment
(``moe_expert_share = (index, of)``, ``num_experts`` held of
``moe_router_experts``): the router keeps its whole width and its 8 a token,
the weights are normalised over all 8, and only the held experts' terms are
summed, with no stand-in for the absent ones; the shared expert is whole. The
vocabulary may be a slice; embedding and head are then that slice.

Same interface as every reference: ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``; weights in the program's own tree
(``leading[i]/mixer``, ``leading[i]/ffn``, ``periods/<kind>/...``,
``periods/moe/...``, the periods' leaves ``[periods, layers of the kind a
period, ...]``). Computed a sequence at a time, a head at a time and, where
the sequence is whole blocks of 512, 512 queries at a time, so that ``[2,
8704]`` tokens at the published widths fit beside a resident serving program:
a block's float32 scores are ``512 x 8704 x 4 B`` = 18 MB, the selection's
mask ``8704 x 8704`` = 76 MB a layer.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BLOCK = 512  # queries a block of scores holds, where the sequence is whole blocks


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rotate(x, theta, width=None):
    """``x`` [T, ..., D], token ``i`` at position ``i``: the leading ``width``
    features (all of them: None) rotated, feature ``j`` with ``j + width /
    2``; the rest pass."""
    if width is not None and width < x.shape[-1]:
        return jnp.concatenate([_rotate(x[..., :width], theta), x[..., width:]], axis=-1)
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * theta ** (-jnp.arange(half, dtype=F32) / half)  # [T, half]
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle), a * jnp.sin(angle) + b * jnp.cos(angle)], axis=-1)


KINDS = {"sparse_latent": "full", "window_latent": "window"}


def arch_of(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference needs of a configuration file's ``model``
    section; refuses a block this file does not describe."""
    kw = model["kwargs"]
    types = tuple(kw["layer_types"])
    index, of = kw.get("moe_expert_share", (0, 1))
    full = (kw["num_heads"], kw["q_lora_rank"], kw["kv_lora_rank"], kw["qk_nope_head_dim"], kw["qk_rope_head_dim"], kw["v_head_dim"], float(kw["rope_theta"]))
    window = (kw["window_num_heads"], kw["window_q_lora_rank"], kw["window_kv_lora_rank"], kw["window_qk_nope_head_dim"],
              kw["window_qk_rope_head_dim"], kw.get("window_v_head_dim") or kw["v_head_dim"], float(kw["window_rope_theta"]))
    arch = {
        "types": types,
        "leading": kw["leading_dense_layers"],
        "hidden": kw["hidden_size"],
        "full": full,
        "window_dims": window,
        "window": kw["window"],
        "index_heads": kw["index_num_heads"],
        "index_dim": kw["index_head_dim"],
        "index_topk": kw["index_topk"],
        "norm_eps": kw["norm_eps"],
        "held": kw["num_experts"],
        "first_held": index * kw["num_experts"],
        "experts_per_token": kw["moe_top_k"],
        "routed_scaling": float(kw["moe_routed_scaling"]),
    }
    described = (
        len(types) == kw["num_layers"] and set(types) <= set(KINDS) and 0 <= arch["leading"] < len(types)
        and kw["head_dim"] == full[3] + full[4] and kw.get("attn_softmax_scale") is None and min(full[1], window[1]) > 0
        and kw["latent_lora_rescale"] is True and kw["attn_head_gate"] is True
        and kw["norm"] == "rmsnorm" and kw["position"] == "rope" and kw["activation"] == "swiglu"
        and not kw.get("use_bias", False) and not kw["tie_embeddings"] and kw["moe_scoring"] == "sigmoid"
        and kw["moe_select_bias"] is True and kw["moe_norm_topk_prob"] is True and kw["moe_shared_experts"] == 1
        and kw.get("moe_drop_tokens") is False and kw["num_experts"] * of == kw["moe_router_experts"]
    )
    if not described:
        raise ValueError(f"the dots3-note reference does not describe {kw}")
    return arch


def _query_blocks(fn, T):
    """``fn(first query, queries)`` over the sequence's queries, ``BLOCK`` at a time where it is whole blocks: [T, ...]."""
    if T % BLOCK or T == BLOCK:
        return fn(0, T)
    out = jax.lax.map(lambda b: fn(b * BLOCK, BLOCK), jnp.arange(T // BLOCK))
    return out.reshape((T,) + out.shape[2:])


def _selection(arch, p, h, c_q, theta, rope):
    """[T, T] bool: the keys each query attends in a full layer."""
    T = h.shape[0]
    IH, ID, k = arch["index_heads"], arch["index_dim"], arch["index_topk"]
    q = _rotate((c_q @ p["wi_qb"]).reshape(T, IH, ID), theta, rope)  # assumed: the leading qk_rope_head_dim rotated, the layer's theta
    key = _rotate(_layer_norm(h @ p["wi_k"], p["wi_k_norm_scale"], p["wi_k_norm_bias"], arch["norm_eps"]), theta, rope)  # assumed: eps rms_norm_eps
    w = (h @ p["wi_w"]) * (IH ** -0.5 * ID ** -0.5)
    at = jnp.arange(T)

    def block(first, n):
        rows = first + jnp.arange(n)
        qb, wb = jax.lax.dynamic_slice_in_dim(q, first, n), jax.lax.dynamic_slice_in_dim(w, first, n)
        score = lambda total, j: (total + wb[:, j, None] * jax.nn.relu(qb[:, j] @ key.T), None)
        scores, _ = jax.lax.scan(score, jnp.zeros((n, T), F32), jnp.arange(IH))
        causal = at[None, :] <= rows[:, None]
        # a stable argsort of the negated scores: the largest first, equal scores by position; a key's rank is its place in it
        order = jnp.argsort(-jnp.where(causal, scores, -jnp.inf), axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1)
        return causal & (rank < k)

    return _query_blocks(block, T)


@functools.partial(jax.jit, static_argnames=("arch_key", "kind"))
def _mixer(x, p, arch_key, kind):
    """One sequence ``x`` [T, H] through a full or a window layer's mixer, expanded."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    T = x.shape[0]
    N, Cq, C, nope, rope, Dv, theta = arch["full"] if kind == "full" else arch["window_dims"]
    s_q, s_kv = (arch["hidden"] / Cq) ** 0.5, (arch["hidden"] / C) ** 0.5  # assumed: apply_mla_qkv_lora_rescale
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    c_q = s_q * _rms(h @ p["wq_a"], p["q_norm_scale"], arch["norm_eps"])
    kv = h @ p["wkv_a"]
    c_kv = s_kv * _rms(kv[:, :C], p["kv_norm_scale"], arch["norm_eps"])  # the norm over the latent alone
    k_rope = _rotate(kv[:, C:], theta)  # [T, rope]: one for all heads
    at = jnp.arange(T)
    if kind == "full":
        seen = _selection(arch, p, h, c_q, theta, rope)
    else:
        seen = (at[None, :] <= at[:, None]) & (at[:, None] - at[None, :] < arch["window"])  # assumed: 513 counts the query's own position
    gate = jax.nn.sigmoid(h @ p["wg_head"])  # [T, N]

    def one_head(out, args):
        wq, wk, wv, wo, g = args  # [Cq, nope + rope], [C, nope], [C, Dv], [Dv, H]: the head's own columns and rows; its gate [T]
        q, kn, v = c_q @ wq, c_kv @ wk, c_kv @ wv
        qn, qr = q[:, :nope], _rotate(q[:, nope:], theta)

        def block(first, n):
            scores = (jax.lax.dynamic_slice_in_dim(qn, first, n) @ kn.T + jax.lax.dynamic_slice_in_dim(qr, first, n) @ k_rope.T) / jnp.sqrt(F32(nope + rope))
            return jax.nn.softmax(jnp.where(jax.lax.dynamic_slice_in_dim(seen, first, n), scores, -jnp.inf), axis=-1) @ v

        # concat_n(g_n o_n) Wo as the sum over heads of (g_n o_n) Wo,n: the same product, a head's [T, Dv] at a time
        return out + (g[:, None] * _query_blocks(block, T)) @ wo, None

    by_head = lambda w, width: w.reshape(w.shape[0], N, width).transpose(1, 0, 2)
    heads = (by_head(p["wq_b"], nope + rope), by_head(p["wk_b"], nope), by_head(p["wv_b"], Dv), p["wo"].reshape(N, Dv, -1), gate.T)
    out, _ = jax.lax.scan(one_head, jnp.zeros_like(x), heads)
    return x + out


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, p, eps):
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    h = _rms(x, p["mlp_norm_scale"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_out"]


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _router(x, p, arch_key):
    """The second norm, each token's weight for each routed expert [T, E] (its
    normalised score where chosen, zero elsewhere) and the shared expert's
    output."""
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
    periods, types, lead = params["periods"], arch["types"], arch["leading"]
    x = params["embed"]["tokens"][tokens].astype(F32)
    for kind, p in zip(types, params.get("leading", ())):
        x = _mixer(x, p["mixer"], arch_key=key, kind=KINDS[kind])
        x = _dense_ffn(x, p["ffn"], eps=arch["norm_eps"])
    body = types[lead:]
    period = next(n for n in range(1, len(body) + 1) if len(body) % n == 0 and all(body[i] == body[i % n] for i in range(len(body))))
    for i, kind in enumerate(body):
        per, j = divmod(i, period)
        jk = body[per * period : per * period + j].count(kind)  # the layer's place among its kind in the period
        x = _mixer(x, jax.tree_util.tree_map(lambda a: a[per, jk], periods[kind]), arch_key=key, kind=KINDS[kind])
        moe = periods["moe"]
        own = jax.tree_util.tree_map(lambda a: a[per, j], {k: v for k, v in moe.items() if k != "experts"})
        h, weights, out = _router(x, own, arch_key=key)
        for e in range(arch["held"]):  # the held experts' terms of the 8-term sum
            w = (moe["experts"][name][per, j, e] for name in ("w_gate", "w_up", "w_out"))
            out = _add_expert(out, h, weights[..., arch["first_held"] + e], *w)
        x = x + out
    return _head(x, params["final_norm_scale"], params["lm_head"], eps=arch["norm_eps"])


def logits(model: Dict[str, Any], params, tokens):
    """tokens [B, T] int32 -> float32 logits [B, T, vocabulary held]."""
    arch = arch_of(model)
    key = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        # a sequence's logits leave the device before the next is computed: two of [8704, 19008] float32 and their stack
        # were 2.6 GB beside a resident serving program that leaves 4.8 (peak 16.2 of 16.9 GB, PR 66's first chip run)
        return np.stack([np.asarray(_sequence(arch, key, params, jnp.asarray(row))) for row in tokens])


def loss(model: Dict[str, Any], params, tokens):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1]."""
    lg = logits(model, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
