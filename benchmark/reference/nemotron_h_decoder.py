"""The plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B's blocks
(``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` ``config.json``, ``model_type:
nemotron_h``; the catalog describes it as "Mamba-2 (64 heads, conv4); GQA
32Q/2KV - 52 blocks MEMEM*... (M=Mamba, E=MoE, *=attn x6); 128 experts, top-6,
1 shared; relu^2, routed scaling 2.5"): the forward pass and next-token loss in
straightforward ``jax.numpy`` and float32, matrix multiplications at precision
``highest``. No kernel, no cache, no pages, no chunk form, no tiles, no sorted
rows: the state-space recurrence is a plain ``lax.scan`` over time,
TOKEN BY TOKEN; attention is full causal attention over the whole sequence a
head at a time; every held expert runs over ALL tokens, one expert at a time,
and its output is weighted by a mask that is zero where the router did not
choose it.
Nothing is imported from the program under test.

A block is ONE sublayer (``x`` the residual stream, every norm an RMSNorm with
a learned scale, ``norm_eps`` 1e-5; no bias anywhere but the convolution's):

    x = E[token]
    every block:  x = x + f(RMSNorm(x)),  f by the block's letter of hybrid_override_pattern
    logits = RMSNorm(x) W_head                          untied head

``M``, the Mamba-2 mixer (``d_inner`` = 64 heads x 64 = 4,096, NOT ``expand`` x
2,688; state ``N`` = 128; EIGHT groups of ``B`` and ``C``; 4 taps):

    [z (4096) ; xBC (6144) ; dt (64)] = h W_in          W_in's three parts are three leaves of the program's tree
    xBC = silu(conv(xBC) + conv_bias)     depthwise causal convolution over all 6,144 channels, zeros before the sequence
    x [64, 64], B [8, 128], C [8, 128] = split(xBC)      head n reads group n // 8
    dt = softplus(dt + dt_bias)   a head;  A = -exp(A_log)   a head;  no clamp on dt
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_{g,t}^T       S [64, 64, 128] float32, S_0 = 0
    y_t = S_t C_{g,t} + D x_t                            D a head
    f = RMSNorm_groups(y * silu(z)) W_out                the gate BEFORE the norm, the norm over each of the
                                                         8 groups of 512 features APART, one learned scale [4096]

``*``, attention (32 query heads over 2 KV heads of 128):

    q k v = h Wq, h Wk, h Wv          NO positional term of any kind, no gate, no QK-norm, no bias
    s(i, j) = 128^-0.5 q_i . k_j,  j <= i
    f = concat_heads(softmax_j(s) v) Wo

``E``, the expert FFN (a router of 128, 6 a token, experts of TWO matrices):

    s = sigmoid(h W_r)   float32;   chosen = the 6 largest of s + selection bias
    w_e = 2.5 * s_e / sum_{chosen} s   for e chosen, else 0       norm_topk_prob, routed_scaling_factor
    f = sum_e w_e relu(h W_up,e)^2 W_down,e  +  relu(h W_up,s)^2 W_down,s     widths 1,856 and (shared) 3,712; NO gate matrix

THE SHARE. The ``model`` section may hold one chip's share of a deployment
(``moe_expert_share = (index, of)``, ``num_experts`` held of
``moe_router_experts``): the router keeps its 128 outputs and its 6 a token,
the weights are normalised over all 6, and only the held experts' terms are
summed, with no stand-in for the absent ones; the shared expert is whole. The
vocabulary may be a slice (``vocab_size`` rows of the table and of the head):
ids, logits and the loss are over the slice.

ASSUMED (each also under ``assumed`` in the configuration file): no positional
term in the attention blocks (the family's modelling code builds no rotary
embedding for ``nemotron_h`` and the Nemotron-H report, arXiv:2504.03624, says
the model has no position embeddings; ``rope_theta`` and
``partial_rotary_factor`` are carried by the config and unused); no clamp on
``dt`` (the config names no ``time_step_limit``; ``time_step_min / max /
floor`` are the initialiser's); the float32 state; ``d_inner`` read off the head
keys; the shared expert as ONE FFN of 3,712; how the seeded weights draw
``A_log``, ``dt_bias``, ``D``, the taps and their bias
(``deepspeed_tpu/models/hybrid_moe.py``). ``chunk_size`` 128 is the published
kernel's tile and no part of the mathematics: this file has no chunk at all.
LEFT OUT: nothing of a block; the blocks, experts and vocabulary rows that
other chips hold (the configuration file's ``deployment``).

Same interface as every reference: ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``, ``final_states(model, params, tokens)`` for a
direct look at what the state store has to hold, ``router_weights(model,
params, tokens)`` for where every token was sent, and ``ffn_block(model, p,
x)`` for one FFN block's own addend (the share test); weights in the program's own
tree (``periods/ssm/...``, ``periods/softmax/...``, ``periods/moe/...``, the
leaves ``[periods, blocks of that kind a period, ...]``, ``embed/tokens``,
``lm_head``). Computed a sequence at a time, an attention head's scores at a
time, an expert at a time and the head a block of the vocabulary at a time,
each block of logits laid in the HOST's memory where the process has a CPU
backend beside the accelerator.
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
    index, of = kw.get("moe_expert_share", (0, 1))
    arch = {
        "layer_types": types,
        "num_heads": kw["num_heads"],
        "num_kv_heads": kw["num_kv_heads"],
        "head_dim": kw["head_dim"],
        "ssm_heads": kw["ssm_num_heads"],
        "ssm_head_dim": kw["ssm_head_dim"],
        "ssm_state": kw["ssm_state"],
        "ssm_groups": kw["ssm_groups"],
        "norm_eps": kw["norm_eps"],
        "experts_per_token": kw["moe_top_k"],
        "routed_scaling": float(kw["moe_routed_scaling"]),
        "held": kw["num_experts"],
        "first_held": index * kw["num_experts"],
    }
    described = (
        len(types) == kw["num_layers"] and set(types) <= {"ssm", "softmax", "ffn"} and "ffn" in types and set(types) != {"ffn"}
        and not kw.get("leading_dense_layers") and kw["num_experts"] >= 1 and kw["ssm_num_heads"] % kw["ssm_groups"] == 0
        and kw.get("ssm_conv_kernel", 4) >= 2 and kw["norm"] == "rmsnorm" and kw["position"] == "none" and kw["activation"] == "relu2"
        and not kw.get("use_bias", False) and kw["tie_embeddings"] is False and kw.get("attn_softmax_scale") is None
        and not kw.get("attn_output_gate") and not kw.get("attn_head_gate") and kw.get("v_head_dim", 0) in (0, kw["head_dim"])
        and kw.get("attn_value_scale", 1.0) == 1.0 and kw["moe_scoring"] == "sigmoid" and kw["moe_select_bias"] is True
        and kw["moe_norm_topk_prob"] is True and kw["moe_shared_experts"] >= 1
        and kw.get("moe_router_experts", kw["num_experts"] * of) == kw["num_experts"] * of and 0 <= index < of
        and kw.get("embedding_multiplier", 1.0) == kw.get("residual_multiplier", 1.0) == kw.get("logits_scaling", 1.0) == 1.0
    )
    if not described:
        raise ValueError(f"the Nemotron-H reference does not describe {kw}")
    return arch


def _period_of(types) -> int:
    L = len(types)
    return next(n for n in range(1, L + 1) if L % n == 0 and all(types[i] == types[i % n] for i in range(L)))


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _ssm_mixer(x, p, arch_key):
    """One sequence ``x`` [T, H] through a Mamba-2 block, token by token.
    Returns ``(x, S_T [heads, head_dim, state])``."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    T = x.shape[0]
    NH, P, N, G = arch["ssm_heads"], arch["ssm_head_dim"], arch["ssm_state"], arch["ssm_groups"]
    inner = NH * P
    h = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    z, xbc, dt = h @ p["w_z"], h @ p["w_xbc"], h @ p["w_dt"]  # the published in_proj's three parts, stored apart
    K = p["conv_w"].shape[0]
    ext = jnp.pad(xbc, ((K - 1, 0), (0, 0)))  # zeros before the sequence
    xbc = jax.nn.silu(p["conv_b"] + sum(p["conv_w"][j] * ext[j : j + T] for j in range(K)))
    xs = xbc[:, :inner].reshape(T, NH, P)
    Bm, Cm = xbc[:, inner : inner + G * N].reshape(T, G, N), xbc[:, inner + G * N :].reshape(T, G, N)
    group_of_head = jnp.arange(NH) // (NH // G)  # head n reads group n // (NH / G)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # [T, NH]
    A = -jnp.exp(p["A_log"])  # [NH]

    def step(S, t):
        x_t, B_t, C_t, dt_t = t  # [NH, P], [G, N], [G, N], [NH]
        S = jnp.exp(dt_t * A)[:, None, None] * S + (dt_t[:, None] * x_t)[:, :, None] * B_t[group_of_head][:, None, :]
        return S, jnp.einsum("hpn,hn->hp", S, C_t[group_of_head]) + p["D"][:, None] * x_t

    S, y = jax.lax.scan(step, jnp.zeros((NH, P, N), F32), (xs, Bm, Cm, dt))
    gated = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, G, inner // G)  # the gate BEFORE the norm
    normed = _rms(gated, p["o_norm_scale"].reshape(G, inner // G), arch["norm_eps"]).reshape(T, inner)  # each group apart
    return x + normed @ p["wo"], S


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _attention_mixer(x, p, arch_key):
    """One sequence ``x`` [T, H] through an attention block: full causal GQA, no positional term."""
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
        return jax.nn.softmax(jnp.where(seen, (qh @ kh.T) / jnp.sqrt(F32(D)), -jnp.inf), axis=-1) @ vh

    attn = jax.lax.map(one_head, (q, k, v))  # [NH, T, D]
    return x + attn.transpose(1, 0, 2).reshape(T, NH * D) @ p["wo"], None


def _relu2_ffn(h, w_in, w_out):
    return jnp.square(jax.nn.relu(h @ w_in)) @ w_out


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _router(x, p, arch_key):
    """The block's norm, each token's weight for each of the router's experts
    [T, E] (2.5 times its normalised score where chosen, zero elsewhere: the
    mask) and the shared expert's output."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    h = _rms(x, p["mlp_norm_scale"], arch["norm_eps"])
    s = jax.nn.sigmoid(h @ p["gate"]["wg"])
    _, chosen = jax.lax.top_k(s + p["gate"]["bias"], arch["experts_per_token"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    top = top / jnp.sum(top, axis=-1, keepdims=True) * arch["routed_scaling"]
    weights = jnp.sum(jax.nn.one_hot(chosen, s.shape[-1], dtype=F32) * top[..., None], axis=-2)
    return h, weights, _relu2_ffn(h, p["shared"]["w_in"], p["shared"]["w_out"])


@jax.jit
def _add_expert(acc, h, weight, w_in, w_out):
    """acc + weight * expert(h), EVERY token (the weight is zero where the expert was not chosen); one expert's matrices upcast."""
    return acc + weight[..., None] * _relu2_ffn(h, w_in.astype(F32), w_out.astype(F32))


def ffn_block(model: Dict[str, Any], p, x):
    """One FFN block's addend for ``x`` [T, H] (no residual): the held
    experts' terms and the shared expert's, from the block's own leaves ``p``
    (``mlp_norm_scale``, ``gate``, ``shared``, ``experts`` [held, ...]: ``w_in_t``
    [held, 1856, 2688] and ``w_out`` [held, 1856, 2688])."""
    arch = arch_of(model)
    with jax.default_matmul_precision("highest"):
        return _ffn_block(arch, tuple(sorted(arch.items())), p, jnp.asarray(x, F32))[0]


def _ffn_block(arch, key, p, x):
    """(the block's addend, the router's weights [T, E] over ALL its experts)."""
    h, weights, out = _router(x, {k: v for k, v in p.items() if k != "experts"}, arch_key=key)
    for e in range(arch["held"]):  # the held experts' terms of the 6-term sum
        # the program keeps an expert's input matrix by its output rows, [1856, 2688] (the published up_proj's layout)
        out = _add_expert(out, h, weights[..., arch["first_held"] + e], p["experts"]["w_in_t"][e].T, p["experts"]["w_out"][e])
    return out, weights


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(x, scale, columns, eps):
    """The final norm and a block of the untied head's columns: logits of that block of the vocabulary."""
    return _rms(x, scale.astype(F32), eps) @ columns.astype(F32)


_MIXERS = {"ssm": _ssm_mixer, "softmax": _attention_mixer}


def _host():
    """Where a sequence's logits are laid: the host's memory where there is a CPU backend beside the accelerator."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


class _Block:
    """Block ``[period, j]`` of a stacked leaf, an entry at a time: a block's
    64 held experts are 1.3 GB that would lie beside the stack as a copy."""

    def __init__(self, stack, period, j):
        self.stack, self.at = stack, (period, j)

    def __getitem__(self, e):
        return self.stack[self.at + (e,)]


def _sequence(arch, key, params, tokens):
    """One sequence ``tokens`` [T] -> (logits [T, V], the state-space blocks'
    final states in block order, the FFN blocks' router weights [T, E] in block order)."""
    types = arch["layer_types"]
    n = _period_of(types)
    periods = params["periods"]
    at = lambda tree, period, j: jax.tree_util.tree_map(lambda a: a[period, j], tree)
    states, routed = [], []
    x = params["embed"]["tokens"][tokens].astype(F32)
    for i, kind in enumerate(types):
        period = i // n
        of_kind = types[period * n : i].count(kind)  # which of the period's blocks of this kind
        if kind == "ffn":
            moe = periods["moe"]
            block = at({k: v for k, v in moe.items() if k != "experts"}, period, of_kind)
            block["experts"] = {k: _Block(v, period, of_kind) for k, v in moe["experts"].items()}
            out, weights = _ffn_block(arch, key, block, x)
            x = x + out
            routed.append(weights)
            continue
        x, state = _MIXERS[kind](x, at(periods[kind], period, of_kind), arch_key=key)
        if state is not None:
            states.append(state)
    host = _host()
    head = params["lm_head"]
    blocks = []
    for start in range(0, head.shape[1], VOCAB_BLOCK):
        block = _head_block(x, params["final_norm_scale"], head[:, start : start + VOCAB_BLOCK], eps=arch["norm_eps"])
        blocks.append(block if host is None else jax.device_put(block, host))
    return jnp.concatenate(blocks, axis=-1), states, routed


def _forward(model, params, tokens):
    arch = arch_of(model)
    key = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        rows = [_sequence(arch, key, params, jnp.asarray(row)) for row in np.asarray(tokens)]
    stacked = lambda which: [jnp.stack(block) for block in zip(*(row[which] for row in rows))]
    return jnp.stack([row[0] for row in rows]), stacked(1), stacked(2)


def logits(model: Dict[str, Any], params, tokens):
    """tokens [B, T] int32 -> float32 logits [B, T, vocabulary held]."""
    return _forward(model, params, tokens)[0]


def final_states(model: Dict[str, Any], params, tokens):
    """The recurrent state ``S_T`` [B, heads, head_dim, state] of every
    state-space block after the whole of ``tokens`` (every row the same
    length), in block order: what a served row's entries of the state store
    have to hold."""
    return _forward(model, params, tokens)[1]


def router_weights(model: Dict[str, Any], params, tokens):
    """Each FFN block's router weights [B, T, E] over ALL the router's experts
    (2.5 times the normalised score where chosen, zero elsewhere), in block
    order: which experts a token was sent to, held here or not."""
    return _forward(model, params, tokens)[2]


def loss(model: Dict[str, Any], params, tokens):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1]."""
    lg = logits(model, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    gold = jnp.take_along_axis(logp, jnp.asarray(tokens)[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
