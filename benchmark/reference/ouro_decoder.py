"""The plain reference of Ouro-2.6B's block (``ByteDance/Ouro-2.6B``
``config.json``, ``model_type: ouro``; the catalog's tag: *layers run several
times*; "Scaling Latent Reasoning via Looped Language Models"): the forward
pass, the exit distribution and the next-token loss in straightforward
``jax.numpy`` and float32, matrix multiplications at precision ``highest``.
Two Python ``for`` loops (passes, layers), no cache, no pages, no scan, no
kernel; nothing imported from the program under test.

All norms are RMSNorm ``x * rsqrt(mean(x^2) + eps) * scale``; no bias anywhere
but the exit gate's. ``h`` is the residual stream:

    h = E[token]
    for pass t = 0 .. total_ut_steps - 1, for layer l = 0 .. L - 1, the SAME weights in every pass:
        a = N1_l(h);  q, k, v = a Wq_l, a Wk_l, a Wv_l           16 heads of 128 each, no grouping
        q, k rotated over all 128 features, half-split pairs (i, i + 64), angle pos * theta^(-i / 64)
        o = causal softmax(q . k / sqrt(128)) v                  over THIS pass's k and v alone
        h = h + N2_l(o Wo_l)                                     the sandwich: a norm on the sublayer's output
        h = h + N4_l((silu(N3_l(h) Wg_l) * (N3_l(h) Wu_l)) Wd_l)
      after the last layer of EVERY pass:  h = N_final(h)         the next pass's input; after the last pass, the head's
    logits = h W_head                                             untied, unscaled

    exit gate, every pass but the last: lambda_t = sigmoid(h w + b) of the pass's normed output,
    p_t = lambda_t * prod_{s < t} (1 - lambda_s), the last pass the remainder.

ASSUMED (each under ``assumed`` in the configuration file, with its pointer
into the published ``modeling_ouro.py``): the four norms a layer and where
they sit, the final norm inside the loop over passes, the gate's form, a cache
of its own for every pass (here: every pass attends to its own keys).
LEFT OUT: nothing; at the published ``early_exit_threshold`` 1 every token
runs every pass and the gate moves no logit.

``wrong`` switches in the blocks the cell's check has to refuse, one at a
time (``WRONG``): a pass too few; every pass attending to pass 0's keys and
values; no norm between passes; the two post-sublayer norms left out; every
weight rounded to float8 e4m3's significand (the nearest precision below the
served bfloat16).

Same interface as every reference: ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``, and ``exit_distribution(model, params,
tokens)``; weights in the program's own tree (``embed/tokens``, ``layers/wq``
with a leading layer axis, ``final_norm_scale``, ``lm_head``, ``exit_gate``),
upcast a layer at a time. Computed a sequence at a time and the head a block
of the vocabulary at a time, each block laid in the HOST's memory where the
process has a CPU backend beside the accelerator: the serving program's
12.7 GB leave a chip little room.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
VOCAB_BLOCK = 16384
WRONG = ("three_passes", "shared_cache", "no_pass_norm", "no_post_norm", "weights_fp8")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x [T, N, D]; position t rotates pair (i, i + D/2) by ``t * theta**(-2i/D)``."""
    T, D = x.shape[0], x.shape[-1]
    half = D // 2
    ang = jnp.arange(T, dtype=F32)[:, None] * theta ** (-jnp.arange(half, dtype=F32) / half)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _fp8(w):
    """``w`` rounded to float8 e4m3's 4-bit significand (its exponent range is not the point)."""
    mantissa, exponent = jnp.frexp(w.astype(F32))
    return jnp.ldexp(jnp.round(mantissa * 16.0) / 16.0, exponent)


def arch_of(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference needs of a configuration file's ``model`` section;
    refuses a block this file does not describe."""
    kw = model["kwargs"]
    heads = kw["num_heads"]
    described = (
        kw["norm"] == "rmsnorm" and kw["position"] == "rope" and kw["activation"] == "swiglu" and not kw.get("use_bias", False)
        and kw["tie_embeddings"] is False and kw.get("post_sublayer_norm") is True and kw.get("exit_gate") is True
        and kw.get("num_loops", 1) > 1 and kw.get("early_exit_threshold", 1.0) == 1.0 and (kw.get("num_kv_heads") or heads) == heads
        and kw.get("rope_dim") is None and kw.get("qk_norm") is None
    )
    if not described:
        raise ValueError(f"the Ouro reference does not describe {kw}")
    return {
        "num_layers": kw["num_layers"],
        "num_loops": kw["num_loops"],
        "num_heads": heads,
        "head_dim": kw.get("head_dim") or kw["hidden_size"] // heads,
        "norm_eps": kw["norm_eps"],
        "rope_theta": float(kw["rope_theta"]),
    }


@functools.partial(jax.jit, static_argnames=("arch_key", "wrong"))
def _layer(x, p, kv, arch_key, wrong):
    """One sequence ``x`` [T, H] through one layer. ``kv``: None, or the keys
    and values to attend to in place of this pass's own (``shared_cache``).
    Returns ``(x, (k, v))``."""
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(_fp8 if wrong == "weights_fp8" else (lambda a: a.astype(F32)), p)
    post = (lambda y, scale: y) if wrong == "no_post_norm" else (lambda y, scale: _rms(y, scale, arch["norm_eps"]))
    T = x.shape[0]
    NH, D = arch["num_heads"], arch["head_dim"]
    a = _rms(x, p["attn_norm_scale"], arch["norm_eps"])
    q = _rope((a @ p["wq"]).reshape(T, NH, D), arch["rope_theta"])
    k = _rope((a @ p["wk"]).reshape(T, NH, D), arch["rope_theta"])
    v = (a @ p["wv"]).reshape(T, NH, D)
    k_seen, v_seen = (k, v) if kv is None else kv
    scores = jnp.einsum("tnd,snd->nts", q, k_seen) / jnp.sqrt(F32(D))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("nts,snd->tnd", probs, v_seen).reshape(T, NH * D)
    x = x + post(o @ p["wo"], p["attn_post_norm_scale"])
    m = _rms(x, p["mlp_norm_scale"], arch["norm_eps"])
    x = x + post((jax.nn.silu(m @ p["w_gate"]) * (m @ p["w_up"])) @ p["w_out"], p["mlp_post_norm_scale"])
    return x, (k, v)


@functools.partial(jax.jit, static_argnames=("eps",))
def _pass_end(x, scale, gate_w, gate_b, eps):
    """The final norm after a pass, and the exit gate's ``lambda`` of its output."""
    x = _rms(x, scale.astype(F32), eps)
    return x, jax.nn.sigmoid(x @ gate_w.astype(F32) + gate_b.astype(F32))


@functools.partial(jax.jit, static_argnames=("wrong",))
def _head_block(x, columns, wrong):
    return x @ (_fp8(columns) if wrong == "weights_fp8" else columns.astype(F32))


def _host():
    """Where a sequence's logits are laid: the host's memory where there is a CPU backend beside the accelerator."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def _sequence(arch, key, params, tokens, wrong):
    """One sequence ``tokens`` [T] -> (logits [T, V], exit distribution [T, passes])."""
    layers, gate = params["layers"], params["exit_gate"]
    table = params["embed"]["tokens"]
    x = (_fp8(table[tokens]) if wrong == "weights_fp8" else table[tokens].astype(F32))
    passes = arch["num_loops"] - (1 if wrong == "three_passes" else 0)
    first_pass_kv = []
    stay, shares = jnp.ones(tokens.shape, F32), []
    for t in range(passes):
        for l in range(arch["num_layers"]):
            kv = first_pass_kv[l] if wrong == "shared_cache" and t else None
            x, made = _layer(x, jax.tree_util.tree_map(lambda a: a[l], layers), kv, arch_key=key, wrong=wrong)
            if wrong == "shared_cache" and not t:
                first_pass_kv.append(made)
        if wrong == "no_pass_norm" and t < passes - 1:
            continue
        x, lam = _pass_end(x, params["final_norm_scale"], gate["w"], gate["b"], eps=arch["norm_eps"])
        if t < passes - 1:
            shares.append(lam * stay)
            stay = stay * (1.0 - lam)
    host = _host()
    head = params["lm_head"]
    blocks = []
    for start in range(0, head.shape[1], VOCAB_BLOCK):
        block = _head_block(x, head[:, start : start + VOCAB_BLOCK], wrong=wrong)
        blocks.append(block if host is None else jax.device_put(block, host))
    return jnp.concatenate(blocks, axis=-1), jnp.stack(shares + [stay], axis=-1)


def _forward(model, params, tokens, wrong: Optional[str]):
    if wrong is not None and wrong not in WRONG:
        raise ValueError(f"unknown wrong block {wrong!r}: one of {WRONG}")
    arch = arch_of(model)
    key = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        rows = [_sequence(arch, key, params, jnp.asarray(row), wrong) for row in np.asarray(tokens)]
    return jnp.stack([lg for lg, _ in rows]), jnp.stack([p for _, p in rows])


def logits(model: Dict[str, Any], params, tokens, wrong: Optional[str] = None):
    """tokens [B, T] int32 -> float32 logits [B, T, vocabulary]. ``wrong``: one of ``WRONG``."""
    return _forward(model, params, tokens, wrong)[0]


def exit_distribution(model: Dict[str, Any], params, tokens):
    """[B, T, passes] float32: the share of each token that leaves after each pass; sums to one."""
    return _forward(model, params, tokens, None)[1]


def loss(model: Dict[str, Any], params, tokens):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1]."""
    lg = logits(model, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    gold = jnp.take_along_axis(logp, jnp.asarray(tokens)[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
