"""The plain reference of granite-4.0-h-micro's block
(``ibm-granite/granite-4.0-h-micro`` ``config.json``, ``model_type:
granitemoehybrid``; the catalog describes it as "Mamba-2 + GQA, dense (no
MoE)"): the forward pass and next-token loss in straightforward ``jax.numpy``
and float32, matrix multiplications at precision ``highest``. No kernel, no
cache, no pages, no chunk form, no tiles: the state-space recurrence is a plain
``lax.scan`` over time, TOKEN BY TOKEN; attention is full causal attention
over the whole sequence a head at a time; a layer's weights are upcast a layer
at a time and the head is computed a block of the vocabulary at a time.
Nothing is imported from the program under test.

``x`` is the residual stream, ``h = RMSNorm(x)`` a sub-block's input (every
norm an RMSNorm with a learned scale, ``rms_norm_eps`` 1e-5; no bias anywhere
but the convolution's):

    x = 12 * E[token]                                   embedding_multiplier
    every layer:  x = x + 0.22 * mixer(RMSNorm(x))      residual_multiplier, BOTH branches
                  x = x + 0.22 * (silu(h Wg) * (h Wu)) Wd,  h = RMSNorm(x)   a dense SwiGLU FFN of 8,192 in EVERY layer
    logits = (RMSNorm(x) E^T) / 8                       logits_scaling; the embedding tied

Attention mixer (layers 5, 15, 25, 35; 32 query heads over 8 KV heads of 64):

    q k v = h Wq, h Wk, h Wv          NO positional term ("nope"), no gate, no QK-norm, no bias
    s(i, j) = 0.015625 * q_i . k_j,  j <= i             attention_multiplier = 1/64, NOT 64^-0.5
    mixer = concat_heads(softmax_j(s) v) Wo

Mamba-2 mixer (the other 36 layers; ``d_inner`` = 2 x 2048 = 4,096 = 64 heads x
64, state ``N`` = 128, ONE group, 4 taps):

    [z (4096) ; xBC (4352) ; dt (64)] = h W_in          W_in's three parts are three leaves of the program's tree
    xBC = silu(conv(xBC) + conv_bias)     depthwise causal convolution, zeros before the sequence
    x [64, 64], B [128], C [128] = split(xBC)            B and C shared by all heads
    dt = softplus(dt + dt_bias)   a head;  A = -exp(A_log)   a head;  no clamp on dt
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T           S [64, 64, 128] float32, S_0 = 0
    y_t = S_t C_t + D x_t                                D a head
    mixer = RMSNorm_4096(y * silu(z)) W_out              the gate BEFORE the norm, the norm over all 4,096

ASSUMED (each also under ``assumed`` in the configuration file): the float32
state; no clamp on ``dt`` (the config names none); how the seeded weights draw
``A_log``, ``dt_bias``, ``D``, the taps and their bias
(``deepspeed_tpu/models/hybrid_moe.py``). ``mamba_chunk_size`` 256 is the
published kernel's tile and no part of the mathematics: this file has no chunk
at all. LEFT OUT: nothing.

Same interface as every reference: ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``, and ``final_states(model, params, tokens)`` for
a direct look at what the state store has to hold; weights in the program's own
tree (``periods/ssm/...``, ``periods/softmax/...``, ``periods/ffn/...``, the
leaves ``[periods, layers of that kind a period, ...]``, ``embed/tokens``).
Computed a sequence at a time, an attention head's scores at a time and the
head a block of the vocabulary at a time, each block laid in the HOST's memory
where the process has a CPU backend beside the accelerator: ``[4, 1536]``
tokens x 100,352 logits are 2.5 GB, which a chip that holds the serving
program's 13 GB has no room for.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
VOCAB_BLOCK = 16384


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def arch_of(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference needs of a configuration file's ``model`` section;
    refuses a block this file does not describe."""
    kw = model["kwargs"]
    types = tuple(kw["layer_types"])
    arch = {
        "layer_types": types,
        "num_heads": kw["num_heads"],
        "num_kv_heads": kw["num_kv_heads"],
        "head_dim": kw["head_dim"],
        "softmax_scale": float(kw["attn_softmax_scale"]),
        "ssm_heads": kw["ssm_num_heads"],
        "ssm_head_dim": kw["ssm_head_dim"],
        "ssm_state": kw["ssm_state"],
        "norm_eps": kw["norm_eps"],
        "embedding_multiplier": float(kw["embedding_multiplier"]),
        "residual_multiplier": float(kw["residual_multiplier"]),
        "logits_scaling": float(kw["logits_scaling"]),
    }
    described = (
        len(types) == kw["num_layers"] and set(types) <= {"ssm", "softmax"} and kw["num_experts"] == 0
        and not kw.get("leading_dense_layers") and kw.get("ssm_groups", 1) == 1 and kw.get("ssm_conv_kernel", 4) >= 2
        and kw["norm"] == "rmsnorm" and kw["position"] == "none" and kw["activation"] == "swiglu"
        and not kw.get("use_bias", False) and kw["tie_embeddings"] is True and not kw.get("attn_output_gate")
        and not kw.get("attn_head_gate") and kw.get("v_head_dim", 0) in (0, kw["head_dim"]) and kw.get("attn_value_scale", 1.0) == 1.0
    )
    if not described:
        raise ValueError(f"the granite hybrid reference does not describe {kw}")
    return arch


def _period_of(types) -> int:
    L = len(types)
    return next(n for n in range(1, L + 1) if L % n == 0 and all(types[i] == types[i % n] for i in range(L)))


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _ssm_mixer(x, p, arch_key):
    """One sequence ``x`` [T, H] through a Mamba-2 layer's mixer, token by
    token. Returns ``(x, S_T [heads, head_dim, state])``."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    T = x.shape[0]
    NH, P, N = arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"]
    inner = NH * P
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    z, xbc, dt = h @ p["w_z"], h @ p["w_xbc"], h @ p["w_dt"]  # the published in_proj's three parts, stored apart
    K = p["conv_w"].shape[0]
    ext = jnp.pad(xbc, ((K - 1, 0), (0, 0)))  # zeros before the sequence
    xbc = jax.nn.silu(p["conv_b"] + sum(p["conv_w"][j] * ext[j : j + T] for j in range(K)))
    xs, Bm, Cm = xbc[:, :inner].reshape(T, NH, P), xbc[:, inner : inner + N], xbc[:, inner + N :]
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [T, NH]
    A = -jnp.exp(p["A_log"])  # [NH]

    def step(S, t):
        x_t, B_t, C_t, dt_t = t  # [NH, P], [N], [N], [NH]
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        return S, jnp.einsum("hpn,n->hp", S, C_t) + p["D"][:, None] * x_t

    S, y = jax.lax.scan(step, jnp.zeros((NH, P, N), F32), (xs, Bm, Cm, dt))
    gated = _rms(y.reshape(T, inner) * jax.nn.silu(z), p["o_norm_scale"], arch["norm_eps"])  # the gate BEFORE the norm
    return x + arch["residual_multiplier"] * (gated @ p["wo"]), S


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _attention_mixer(x, p, arch_key):
    """One sequence ``x`` [T, H] through an attention layer's mixer: full causal GQA, no positional term."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    T = x.shape[0]
    NH, NKV, D = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    q = (h @ p["wq"]).reshape(T, NH, D).transpose(1, 0, 2)
    k = jnp.repeat((h @ p["wk"]).reshape(T, NKV, D).transpose(1, 0, 2), NH // NKV, axis=0)  # query head n reads KV head n // group
    v = jnp.repeat((h @ p["wv"]).reshape(T, NKV, D).transpose(1, 0, 2), NH // NKV, axis=0)
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]

    def one_head(args):
        qh, kh, vh = args  # [T, D] each
        return jax.nn.softmax(jnp.where(seen, arch["softmax_scale"] * (qh @ kh.T), -jnp.inf), axis=-1) @ vh

    attn = jax.lax.map(one_head, (q, k, v))  # [NH, T, D]
    return x + arch["residual_multiplier"] * (attn.transpose(1, 0, 2).reshape(T, NH * D) @ p["wo"]), None


@functools.partial(jax.jit, static_argnames=("eps", "by"))
def _dense_ffn(x, p, eps, by):
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    h = _rms(x, p["mlp_norm_scale"], eps)
    return x + by * ((jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_out"])


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _head_block(x, scale, rows, eps, scaling):
    """The final norm and a block of the tied head's rows: logits of that block of the vocabulary, divided."""
    return (_rms(x, scale.astype(F32), eps) @ rows.astype(F32).T) / scaling


_MIXERS = {"ssm": _ssm_mixer, "softmax": _attention_mixer}


def _host():
    """Where a sequence's logits are laid: the host's memory where there is a CPU backend beside the accelerator."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def _sequence(arch, key, params, tokens):
    """One sequence ``tokens`` [T] -> (logits [T, V], the state-space layers' final states in layer order)."""
    types = arch["layer_types"]
    n = _period_of(types)
    periods = params["periods"]
    at = lambda tree, period, j: jax.tree_util.tree_map(lambda a: a[period, j], tree)
    states = []
    table = params["embed"]["tokens"]
    x = arch["embedding_multiplier"] * table[tokens].astype(F32)
    for i, kind in enumerate(types):
        period, j = divmod(i, n)
        of_kind = types[period * n : i].count(kind)  # which of the period's layers of this kind
        x, state = _MIXERS[kind](x, at(periods[kind], period, of_kind), arch_key=key)
        if state is not None:
            states.append(state)
        x = _dense_ffn(x, at(periods["ffn"], period, j), eps=arch["norm_eps"], by=arch["residual_multiplier"])
    host = _host()
    blocks = []
    for start in range(0, table.shape[0], VOCAB_BLOCK):
        block = _head_block(x, params["final_norm_scale"], table[start : start + VOCAB_BLOCK], eps=arch["norm_eps"], scaling=arch["logits_scaling"])
        blocks.append(block if host is None else jax.device_put(block, host))
    return jnp.concatenate(blocks, axis=-1), states


def _forward(model, params, tokens):
    arch = arch_of(model)
    key = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        rows = [_sequence(arch, key, params, jnp.asarray(row)) for row in np.asarray(tokens)]
    return jnp.stack([lg for lg, _ in rows]), [jnp.stack(layer) for layer in zip(*(states for _, states in rows))]


def logits(model: Dict[str, Any], params, tokens):
    """tokens [B, T] int32 -> float32 logits [B, T, vocabulary]."""
    return _forward(model, params, tokens)[0]


def final_states(model: Dict[str, Any], params, tokens):
    """The recurrent state ``S_T`` [B, heads, head_dim, state] of every
    state-space layer after the whole of ``tokens`` (every row the same
    length), in layer order: what a served row's entries of the state store
    have to hold."""
    return _forward(model, params, tokens)[1]


def loss(model: Dict[str, Any], params, tokens):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1]."""
    lg = logits(model, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    gold = jnp.take_along_axis(logp, jnp.asarray(tokens)[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
