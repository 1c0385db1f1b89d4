"""The plain reference: a decoder-only transformer's forward pass and
next-token loss in straightforward ``jax.numpy`` and float32, matrix
multiplications at precision ``highest``. No kernel, no cache, no batching
tricks, no scan; nothing imported from the program under test.

It covers the two published blocks the benchmark's configurations use:

* GPT-2 (Radford et al. 2019; HF ``GPT2Model``): learned positions,
  pre-LayerNorm, biased projections, ``gelu_new`` (the tanh form), head tied
  to the token embedding;
* Mistral-7B (Jiang et al. 2023; HF ``MistralModel``): RMSNorm, rotary
  positions in the half-split ("rotate_half") layout with base
  ``rope_theta``, grouped-query attention, SwiGLU, no biases, untied head,
  no sliding window in v0.3.

A configuration file names its reference (``model.reference``); every
reference has the same two functions, ``logits(model, params, tokens)`` and
``loss(model, params, tokens)``, where ``model`` is the file's ``model``
section. A family this file does not describe gets a reference file of its
own beside it.

Weights come in the program's own tree (``embed/tokens``, ``layers/wq`` with
a leading layer axis, ...), because that is where the seeded weights live;
the reference upcasts one layer at a time so that it fits beside a sharded
training state. Departures from the papers: none in the mathematics; dropout
is zero in every configuration and is not implemented.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _norm(arch: Dict, x, scale, bias):
    if arch["norm"] == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + arch["norm_eps"]) * scale
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + arch["norm_eps"]) * scale
    return out if bias is None else out + bias


def _rope(x, theta: float):
    """x [B, T, N, D]; position t rotates pair (i, i + D/2) by
    ``t * theta**(-2i/D)``."""
    T, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _layer(x, p, arch_key):
    arch = dict(arch_key)
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    B, T, H = x.shape
    NH, NKV, D = arch["num_heads"], arch["num_kv_heads"], arch["head_dim"]
    h = _norm(arch, x, p["attn_norm_scale"], p.get("attn_norm_bias"))
    q, k, v = h @ p["wq"], h @ p["wk"], h @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k, v = q.reshape(B, T, NH, D), k.reshape(B, T, NKV, D), v.reshape(B, T, NKV, D)
    if arch["position"] == "rope":
        q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    group = NH // NKV
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("btnd,bsnd->bnts", q, k) / jnp.sqrt(F32(D))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    attn = jnp.einsum("bnts,bsnd->btnd", probs, v).reshape(B, T, NH * D) @ p["wo"]
    if "bo" in p:
        attn = attn + p["bo"]
    x = x + attn
    h = _norm(arch, x, p["mlp_norm_scale"], p.get("mlp_norm_bias"))
    if arch["activation"] == "swiglu":
        inner = jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])
    else:
        inner = h @ p["w_in"]
        if "b_in" in p:
            inner = inner + p["b_in"]
        inner = _gelu_new(inner)
    out = inner @ p["w_out"]
    if "b_out" in p:
        out = out + p["b_out"]
    return x + out


@jax.jit
def _embed(tokens, tok_table, pos_table):
    x = tok_table.astype(F32)[tokens]
    if pos_table is not None:
        x = x + pos_table.astype(F32)[: tokens.shape[1]][None]
    return x


@functools.partial(jax.jit, static_argnames=("arch_key",))
def _head(x, scale, bias, table, arch_key):
    arch = dict(arch_key)
    x = _norm(arch, x, scale.astype(F32), None if bias is None else bias.astype(F32))
    w = table.astype(F32)
    return x @ (w.T if arch["tie_embeddings"] else w)


def arch_of(model: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference needs from a configuration file's ``model``
    section: the published block in ``kwargs``, nothing of the program's
    engineering knobs."""
    kw = model["kwargs"]
    heads = kw["num_heads"]
    arch = {
        "num_layers": kw["num_layers"],
        "num_heads": heads,
        "num_kv_heads": kw.get("num_kv_heads") or heads,
        "head_dim": kw.get("head_dim") or kw["hidden_size"] // heads,
        "norm": kw["norm"],
        "norm_eps": kw["norm_eps"],
        "position": kw["position"],
        "rope_theta": kw.get("rope_theta", 10000.0),
        "activation": kw["activation"],
        "tie_embeddings": kw["tie_embeddings"],
    }
    if arch["norm"] not in ("layernorm", "rmsnorm") or arch["position"] not in ("learned", "rope"):
        raise ValueError(f"the plain reference does not describe {arch}")
    if arch["activation"] not in ("gelu", "swiglu"):
        raise ValueError(f"the plain reference does not describe activation {arch['activation']!r}")
    return arch


def logits(model: Dict[str, Any], params, tokens):
    """tokens [B, T] int32 -> float32 logits [B, T, vocab]."""
    arch = arch_of(model)
    key = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        x = _embed(tokens, params["embed"]["tokens"], params["embed"].get("pos"))
        for i in range(arch["num_layers"]):
            x = _layer(x, jax.tree_util.tree_map(lambda a: a[i], params["layers"]), arch_key=key)
        table = params["embed"]["tokens"] if arch["tie_embeddings"] else params["lm_head"]
        return _head(x, params["final_norm_scale"], params.get("final_norm_bias"), table, arch_key=key)


def loss(model: Dict[str, Any], params, tokens):
    """Mean next-token cross-entropy of ``tokens`` [B, T + 1]."""
    lg = logits(model, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(gold)
