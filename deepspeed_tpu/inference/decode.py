"""Incremental decoding with a KV cache.

TPU-native counterpart of the reference's fused decoder inference kernels
(``csrc/transformer/inference/csrc/pt_binding.cpp``: ``softmax_context`` =
KV-cache attention, ``qkv_gemm``/``mlp_gemm`` fused projections,
``apply_rotary_pos_emb``, workspace = the preallocated KV cache,
``allocate_workspace`` :1929): one jitted ``prefill`` program consumes the
prompt and fills the cache; one jitted ``decode_step`` program appends a
single token — in-place cache updates via ``dynamic_update_slice`` with
buffer donation, so decoding runs at HBM-bandwidth with no reallocation and
exactly two compiled programs per (batch, max_len) bucket.

Works on the flagship ``TransformerLM`` parameter layout (stacked [L, ...]
layer params, ``models/transformer.py``); numerics are kept in lockstep with
the training forward — guarded by the decode-vs-full-forward parity test
(``tests/unit/inference/test_decode.py``).
"""

from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.compression.int8 import qmatmul
from deepspeed_tpu.models.config import TransformerConfig, cache_layers, has_latent_layers, has_state_layers
from deepspeed_tpu.models.transformer import _norm, _rope

NEG_INF_F = -1e30  # additive mask for dead beams (finite: keeps fp math NaN-free)


class KVCache(NamedTuple):
    """Preallocated decode workspace (reference allocate_workspace)."""

    k: jax.Array  # [L, B, max_len, NKV, D], L the model's cache layers (models/config.py::cache_layers)
    v: jax.Array  # [L, B, max_len, NKV, D]

    @property
    def max_len(self) -> int:
        return self.k.shape[2]


def init_cache(cfg: TransformerConfig, batch: int, max_len: int, dtype=None) -> KVCache:
    _refuse_state_layers(cfg, "generate() / beam_generate() (the dense KVCache)")
    if dtype is None:
        dtype = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}[
            cfg.dtype
        ]
    shape = (cache_layers(cfg), batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


def _project_qkv(cfg: TransformerConfig, p, h):
    """Norm + qkv projection for a [B, T, H] slab (same ops as
    models/transformer.py _layer), the heads not yet split: ``[B, T, NH D]``
    and twice ``[B, T, NKV D]``. Column-parallel under TP serving: the
    weights arrive pre-sliced by shard_map (cfg is then the LOCAL view),
    and ``qmatmul`` fuses int8 dequantization when the weights are
    quantized (``compression/int8.py``)."""
    hn = _norm(h, p["attn_norm_scale"], p.get("attn_norm_bias"), cfg.norm, cfg.norm_eps)
    q = qmatmul(hn, p["wq"])
    k = qmatmul(hn, p["wk"])
    v = qmatmul(hn, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(hn.dtype)
        k = k + p["bk"].astype(hn.dtype)
        v = v + p["bv"].astype(hn.dtype)
    if getattr(cfg, "qk_norm", None) == "projection":
        # over the whole projection, before the head split and RoPE: what is
        # cached is the normed, rotated k
        q = _norm(q, p["q_norm_scale"], None, "rmsnorm", cfg.norm_eps)
        k = _norm(k, p["k_norm_scale"], None, "rmsnorm", cfg.norm_eps)
    return q, k, v


def _split_heads(cfg: TransformerConfig, q, k, v):
    B, T, _ = q.shape
    NH, NKV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return q.reshape(B, T, NH, D), k.reshape(B, T, NKV, D), v.reshape(B, T, NKV, D)


def _layer_project_qkv(cfg: TransformerConfig, p, h):
    """``_project_qkv`` with the heads split: ``[B, T, NH, D]`` and twice
    ``[B, T, NKV, D]``."""
    return _split_heads(cfg, *_project_qkv(cfg, p, h))


def _moe_ffn(cfg, p, h, live=None, experts=None, group_offset=0):
    """Eval-mode MoE for a normed [B, T, H] slab: the router's logits in
    float32, then ``moe/layer.py::routed_experts``, the same function
    ``MoE.apply(train=False)`` runs (eval capacity factor, no gate noise, no
    RNG). ``moe_drop_tokens=False`` takes the sorted, grouped path, any k;
    with drops, the capacity einsums (k of 1 or 2). ``live`` ([B, T] bool)
    marks the window's real tokens: a dead slot is not routed, costs no
    expert work and takes no live token's capacity. Group sizes and capacity
    slots are data, so a shifting routing mix never retraces. Expert weights
    may be int8 (``quantize_params_int8``). ``experts`` (default
    ``p["experts"]``) may be a longer stack in which this layer's begin at
    ``group_offset`` (``_paged_forward``). Returns (out [B, T, H], the
    per-expert assignment counts [E])."""
    from deepspeed_tpu.moe.layer import residual_mix, routed_experts

    B, T, H = h.shape
    tokens = h.reshape(-1, H)
    logits = tokens.astype(jnp.float32) @ p["gate"]["wg"].astype(jnp.float32)
    out, _l_aux, counts = routed_experts(
        p["experts"] if experts is None else experts,
        tokens,
        logits,
        k=cfg.moe_top_k,
        activation=cfg.activation,
        drop_tokens=cfg.moe_drop_tokens,
        norm_topk_prob=cfg.moe_norm_topk_prob,
        capacity_factor=cfg.eval_capacity_factor,
        min_capacity=cfg.min_capacity,
        use_rts=cfg.moe_use_rts,
        live=None if live is None else live.reshape(-1),
        group_offset=group_offset,
    )
    return residual_mix(p, tokens, out, cfg.activation).reshape(B, T, H), counts


def _ffn_body(cfg: TransformerConfig, p, x, norm_scale, norm_bias, tp=None, moe_ffn=_moe_ffn):
    """norm → ffn (→ the sandwich's second norm, ``post_sublayer_norm``), NO
    residual — callers place the residual per architecture.
    ``moe_ffn`` is what an MoE layer runs (``_moe_ffn``, which a caller may
    have bound to its live tokens and expert stacks).
    Returns (out, an MoE layer's per-expert assignment counts or None)."""
    from deepspeed_tpu.moe.experts import apply_dense_ffn

    with jax.named_scope("mlp"):
        h = _norm(x, norm_scale, norm_bias, cfg.norm, cfg.norm_eps)
        if "moe" in p:
            if tp is not None:
                raise NotImplementedError(
                    "tensor-parallel MoE serving is not supported: expert "
                    "placement is the 'expert' mesh axis, not a TP weight split"
                )
            return moe_ffn(cfg, p["moe"], h)
        out = apply_dense_ffn(p, h, cfg.activation, tp=tp)
        if getattr(cfg, "post_sublayer_norm", False):
            out = _norm(out, p["mlp_post_norm_scale"], p.get("mlp_post_norm_bias"), cfg.norm, cfg.norm_eps)
        return out, None


def _layer_mlp(cfg: TransformerConfig, p, x, tp=None, moe_ffn=_moe_ffn):
    out, moe_counts = _ffn_body(cfg, p, x, p["mlp_norm_scale"], p.get("mlp_norm_bias"), tp=tp, moe_ffn=moe_ffn)
    return x + out, moe_counts


def _softmax_scale(cfg, head_dim: int) -> float:
    return (
        cfg.attn_softmax_scale
        if getattr(cfg, "attn_softmax_scale", None) is not None
        else 1.0 / float(np.sqrt(head_dim))
    )


def _post_attention(cfg, p, x, attn, tp=None, moe_ffn=_moe_ffn):
    """Output projection + residual placement + mlp — shared tail of every
    cached-attention layer (dense and paged), so the two decode paths can
    never drift on the residual architecture. Under TP serving the output
    projection is row-parallel: each chip holds its heads' slice of
    ``wo``, the partial sums meet in ``tp.row_matmul``'s (chunked,
    optionally quantized) all-reduce, and the bias — replicated — is
    added exactly once, after the reduce. ``moe_ffn`` is for an MoE layer
    (``_ffn_body``). Returns (x, that layer's expert counts or None)."""
    B, T = x.shape[:2]
    with jax.named_scope("attention"):
        a = attn.reshape(B, T, cfg.num_heads * cfg.head_dim)
        attn = (tp.row_matmul(a, p["wo"]) if tp is not None else qmatmul(a, p["wo"]))
        attn = attn.astype(x.dtype)
        if cfg.use_bias:
            attn = attn + p["bo"].astype(x.dtype)
        if getattr(cfg, "post_sublayer_norm", False):
            attn = _norm(attn, p["attn_post_norm_scale"], p.get("attn_post_norm_bias"), cfg.norm, cfg.norm_eps)
    if cfg.parallel_residual:
        # GPT-J/NeoX: mlp branch reads x (shared ln_1 or its own norm),
        # not the attn-updated residual
        norm_scale = p["attn_norm_scale"] if cfg.shared_parallel_norm else p["mlp_norm_scale"]
        norm_bias = (
            p.get("attn_norm_bias") if cfg.shared_parallel_norm else p.get("mlp_norm_bias")
        )
        out, moe_counts = _ffn_body(cfg, p, x, norm_scale, norm_bias, tp=tp, moe_ffn=moe_ffn)
        return x + attn + out, moe_counts
    x = x + attn
    return _layer_mlp(cfg, p, x, tp=tp, moe_ffn=moe_ffn)


def _cached_attention(cfg, q, k_cache, v_cache, q_positions, kv_len_mask, kv_len=None):
    """q [B,T,NH,D] against the full cache [B,S,NKV,D]; positions beyond the
    valid length are masked (the reference softmax_context semantics)."""
    NH, NKV = q.shape[2], k_cache.shape[2]
    scale = _softmax_scale(cfg, q.shape[-1])
    if (
        q.shape[1] == 1
        and kv_len is not None
        and cfg.position != "alibi"
        and k_cache.shape[1] % 256 == 0
    ):
        # single-token decode: the fused ragged kernel reads only live cache
        # blocks (and GQA kv rows once, without any head expansion)
        from deepspeed_tpu.ops.transformer.decode_attention import decode_attention

        out = decode_attention(q[:, 0], k_cache, v_cache, kv_len, scale=scale)
        return out[:, None]
    S = k_cache.shape[1]
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    causal = q_positions[:, None, :, None] >= kv_pos[None, None, None, :]
    valid = kv_len_mask[None, None, None, :] if kv_len_mask is not None else True
    if NKV != NH:
        # GQA: group the queries [B,T,NKV,G,D] against the shared kv rows —
        # an NH-wide jnp.repeat of the cache here would materialize a
        # G-times copy of the whole workspace every decode step
        B, T, _, D = q.shape
        G = NH // NKV
        qg = q.reshape(B, T, NKV, G, D)
        scores = jnp.einsum("btkgd,bskd->bkgts", qg, k_cache).astype(jnp.float32) * scale
        mask = causal & valid  # [B, 1, T, S] -> [B, 1, 1, T, S] under kv/group axes
        scores = jnp.where(mask[:, :, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
        out = jnp.einsum("bkgts,bskd->btkgd", probs, v_cache)
        return out.reshape(B, T, NH, D)
    scores = jnp.einsum("btnd,bsnd->bnts", q, k_cache).astype(jnp.float32) * scale
    scores = jnp.where(causal & valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    return jnp.einsum("bnts,bsnd->btnd", probs, v_cache)


def _forward_with_cache(cfg, params, tokens, cache: KVCache, start_pos):
    """Run [B, T] tokens starting at ``start_pos``, reading+writing the
    cache. Returns (logits_of_last_token, new_cache)."""
    B, T = tokens.shape
    dtype = cache.k.dtype
    x = params["embed"]["tokens"].astype(dtype)[tokens]
    positions = start_pos + jnp.arange(T, dtype=jnp.int32)
    positions_b = jnp.broadcast_to(positions[None, :], (B, T))
    if cfg.position == "learned":
        x = x + params["embed"]["pos"].astype(dtype)[positions][None]

    S = cache.max_len
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    kv_len_mask = kv_pos < (start_pos + T)

    def layer_step(carry, per_layer):
        x = carry
        p, k_cache_l, v_cache_l = per_layer
        q, k_new, v_new = _layer_project_qkv(cfg, p, x)
        if cfg.position == "rope":
            q = _rope(q, positions_b, cfg.rope_theta, cfg.rope_dim)
            k_new = _rope(k_new, positions_b, cfg.rope_theta, cfg.rope_dim)
        k_cache_l = jax.lax.dynamic_update_slice(
            k_cache_l, k_new.astype(k_cache_l.dtype), (0, start_pos, 0, 0)
        )
        v_cache_l = jax.lax.dynamic_update_slice(
            v_cache_l, v_new.astype(v_cache_l.dtype), (0, start_pos, 0, 0)
        )
        attn = _cached_attention(
            cfg, q, k_cache_l, v_cache_l, positions_b, kv_len_mask, kv_len=start_pos + T
        )
        x, _ = _post_attention(cfg, p, x, attn)
        return x, (k_cache_l, v_cache_l)

    loops = getattr(cfg, "num_loops", 1)
    if loops == 1:
        x, (new_k, new_v) = jax.lax.scan(
            layer_step, x, (params["layers"], cache.k, cache.v)
        )
    else:
        # a looped stack: pass t runs the same weights over cache layers
        # t * L .. (t + 1) * L - 1, the final norm between passes
        L = cfg.num_layers
        ks, vs = [], []
        for t in range(loops):
            x, (k_t, v_t) = jax.lax.scan(
                layer_step, x, (params["layers"], cache.k[t * L : (t + 1) * L], cache.v[t * L : (t + 1) * L])
            )
            ks.append(k_t)
            vs.append(v_t)
            if t < loops - 1:
                x = _pass_norm(cfg, params, x)
        new_k, new_v = jnp.concatenate(ks), jnp.concatenate(vs)

    return _final_logits(cfg, params, x)[:, -1, :], KVCache(k=new_k, v=new_v)


def _looped_passes(cfg, params, carry, one_pass):
    """``cfg.num_loops`` passes of a looped stack inside one program:
    ``one_pass(t, carry) -> carry`` (a layer scan; ``carry[0]`` the hidden
    state, the whole pools behind it) under the ``loop_pass`` scope, the final
    norm between two passes."""
    for t in range(cfg.num_loops):
        with jax.named_scope("loop_pass"):
            carry = one_pass(t, carry)
        if t < cfg.num_loops - 1:
            carry = (_pass_norm(cfg, params, carry[0]),) + tuple(carry[1:])
    return carry


def _pass_norm(cfg, params, x):
    """The final norm between two passes of a looped stack: its output is the
    next pass's input (the last pass's is ``_final_logits``' own)."""
    with jax.named_scope("pass_norm"):
        return _norm(x, params["final_norm_scale"], params.get("final_norm_bias"), cfg.norm, cfg.norm_eps)


def _final_logits(cfg, params, x):
    """Final norm + LM head. Under TP serving with an untied vocab-sharded
    head the returned logits are each chip's LOCAL vocab slice — the
    builders resolve greedy tokens through ``tp.argmax`` (global-first-max
    semantics), so full logits never gather."""
    with jax.named_scope("head_sample"):
        x = _norm(
            x, params["final_norm_scale"], params.get("final_norm_bias"), cfg.norm, cfg.norm_eps
        )
        if cfg.tie_embeddings:
            logits = x @ params["embed"]["tokens"].astype(x.dtype).T
        else:
            logits = qmatmul(x, params["lm_head"])
            if cfg.lm_head_bias:
                logits = logits + params["lm_head_bias"].astype(logits.dtype)
        scaling = getattr(cfg, "logits_scaling", 1.0)  # models/hybrid_moe.py: the logits DIVIDED by it; 1.0 traces nothing
        if scaling != 1.0:
            logits = logits * jnp.asarray(1.0 / scaling, logits.dtype)
    return logits


def _cfg_key(cfg) -> Tuple:
    """Value-based cache key: ``id(cfg)`` could serve a stale compiled
    program if a config object is garbage-collected and another allocated
    at the recycled address."""
    import dataclasses

    try:
        return (
            type(cfg).__name__,
            tuple(
                (f.name, repr(getattr(cfg, f.name, None)))
                for f in dataclasses.fields(cfg)
            ),
        )
    except TypeError:
        return (type(cfg).__name__, repr(cfg))


_decoder_cache: Dict[Tuple, Tuple] = {}


def _refuse_state_layers(cfg, what: str) -> None:
    """Every key and value of a row is the only state ``what`` knows of. A
    model with recurrent-state, short-convolution, sliding-window or latent-attention layers
    (``layer_types`` naming ``linear``, ``ssm``, ``conv``, ``window``, ``window_latent``, ``latent`` or ``sparse_latent``) is refused
    where it is built, with the missing piece named."""
    if has_state_layers(cfg):
        raise NotImplementedError(
            f"{what} does not support a model with recurrent-state (linear-attention or state-space), short-convolution or sliding-window layers: "
            "it would need a snapshot of each row's recurrent state and convolution tail (a conv layer's: the tail alone), or a window layer's "
            "masks, sinks and heads of their own (a window_latent layer's: its ring of latents), beside its keys and values, which only the paged server's "
            "per-slot store keeps (serve through init_inference(...).serve())"
        )
    if has_latent_layers(cfg):
        raise NotImplementedError(
            f"{what} does not support a model with latent-attention layers: a row's latents live in the paged "
            "server's latent pages (kv_pool.StateStore.latent, one entry a token and no value array; a sparse_latent layer's "
            "indexer keys beside them, StateStore.index), which this "
            "path neither allocates, copies, rolls back nor reads (serve through init_inference(...).serve())"
        )


def _jit(fn, telemetry, name, **jit_kwargs):
    """jax.jit, counted under ``name`` when a CompileTelemetry is given —
    the engines' compile_stats() path (profiling/compile_telemetry.py)."""
    if telemetry is None:
        return jax.jit(fn, **jit_kwargs)
    return telemetry.instrument(name, fn, **jit_kwargs)


def _telemetry_uid(telemetry):
    """Program-cache key component: compiled callables built against one
    telemetry registry must not be served to another engine's registry."""
    return None if telemetry is None else telemetry.uid


def build_decoder(cfg: TransformerConfig, telemetry=None) -> Tuple[Any, Any]:
    """(prefill, decode_step) jitted pair for a model config.

    ``prefill(params, tokens, cache)`` consumes the prompt [B, T];
    ``decode_step(params, token, cache, pos)`` appends one token [B].
    Both donate the cache buffer (in-place workspace update).
    """
    _refuse_state_layers(cfg, "generate() / beam_generate() (the dense KVCache)")
    key = (_cfg_key(cfg), _telemetry_uid(telemetry))
    if key in _decoder_cache:
        return _decoder_cache[key]

    prefill = _jit(
        lambda params, tokens, cache: _forward_with_cache(
            cfg, params, tokens, cache, jnp.int32(0)
        ),
        telemetry,
        "kv_prefill",
        donate_argnums=(2,),
    )
    decode_step = _jit(
        lambda params, token, cache, pos: _forward_with_cache(
            cfg, params, token[:, None], cache, pos
        ),
        telemetry,
        "kv_decode_step",
        donate_argnums=(2,),
    )
    _decoder_cache[key] = (prefill, decode_step)
    return prefill, decode_step


# LRU-bounded: serving/rollout loops with varying prompt lengths would
# otherwise retain one whole-loop executable per (lengths, sampling) bucket
# for the process lifetime
_loop_cache: "OrderedDict[Tuple, Any]" = OrderedDict()
_LOOP_CACHE_MAX = 32


def _loop_cache_get(key):
    loop = _loop_cache.get(key)
    if loop is not None:
        _loop_cache.move_to_end(key)
    return loop


def _loop_cache_put(key, loop):
    _loop_cache[key] = loop
    while len(_loop_cache) > _LOOP_CACHE_MAX:
        _loop_cache.popitem(last=False)


def generate(
    cfg: TransformerConfig,
    params,
    input_ids,
    max_new_tokens: int,
    eos_token_id=None,
    temperature: float = 0.0,
    rng=None,
    top_k: int = 0,
    top_p: float = 1.0,
    pad_token_id: int = 0,
    dtype=None,
    telemetry=None,
):
    """KV-cached generation: one jitted prefill + ONE jitted decode loop.

    The whole token-by-token loop is a single compiled ``lax.while_loop``
    program — sampling (greedy / temperature / top-k / top-p,
    ``inference/sampling.py``) and the EOS check run on device, so the only
    host round-trip of the entire generation is fetching the final token
    array. The loop exits early on device once every row has emitted EOS
    (rows finished earlier keep emitting EOS as padding).

    Replaces the reference's per-token kernel-launch loop
    (``deepspeed/inference/engine.py:578`` → HF generate) — same sampling
    controls, but batched into two XLA programs per (batch, lengths,
    sampling-config) bucket.
    """
    import functools

    from deepspeed_tpu.inference.sampling import sample_logits

    tokens = jnp.asarray(input_ids)
    if tokens.ndim == 1:
        tokens = tokens[None, :]
    B, prompt_len = tokens.shape
    max_len = prompt_len + max_new_tokens
    cache = init_cache(cfg, B, max_len, dtype=dtype)
    prefill, _ = build_decoder(cfg, telemetry)
    logits, cache = prefill(params, tokens, cache)
    if rng is None:
        # no rng = greedy (matching sample_logits), never a silently fixed
        # key masquerading as randomness; the carry still needs a key object
        temperature = 0.0
        rng = jax.random.PRNGKey(0)

    key = (
        _cfg_key(cfg), B, prompt_len, max_new_tokens, eos_token_id,
        float(temperature), int(top_k), float(top_p), int(pad_token_id),
        str(tokens.dtype), str(cache.k.dtype), _telemetry_uid(telemetry),
    )
    loop = _loop_cache_get(key)
    if loop is None:
        sample = functools.partial(
            sample_logits, temperature=temperature, top_k=top_k, top_p=top_p
        )

        def _loop(params, logits, cache, rng, out):
            def cond(c):
                step, _, _, _, _, finished = c
                return jnp.logical_and(
                    step < max_new_tokens, jnp.logical_not(jnp.all(finished))
                )

            def body(c):
                step, logits, cache, rng, out, finished = c
                rng, sub = jax.random.split(rng)
                tok = sample(logits, sub).astype(out.dtype)
                if eos_token_id is not None:
                    tok = jnp.where(
                        finished, jnp.asarray(eos_token_id, out.dtype), tok
                    )
                out = jax.lax.dynamic_update_slice(
                    out, tok[:, None], (0, prompt_len + step)
                )
                if eos_token_id is not None:
                    finished = finished | (tok == eos_token_id)
                logits, cache = _forward_with_cache(
                    cfg, params, tok[:, None], cache, prompt_len + step
                )
                return (step + 1, logits, cache, rng, out, finished)

            state = (
                jnp.int32(0), logits, cache, rng, out, jnp.zeros((B,), bool)
            )
            step, _, cache, _, out, _ = jax.lax.while_loop(cond, body, state)
            # the final cache is returned (and ignored by the caller) so the
            # donated input cache can alias an output instead of being copied
            # into the loop carry
            return out, step, cache

        loop = _jit(_loop, telemetry, "kv_decode_loop", donate_argnums=(2, 4))
        _loop_cache_put(key, loop)

    out0 = jnp.full((B, max_len), pad_token_id, tokens.dtype)
    out0 = jax.lax.dynamic_update_slice(out0, tokens, (0, 0))
    out, n_emitted, _ = loop(params, logits, cache, rng, out0)
    return out[:, : prompt_len + int(jax.device_get(n_emitted))]


def beam_generate(
    cfg: TransformerConfig,
    params,
    input_ids,
    max_new_tokens: int,
    num_beams: int = 4,
    eos_token_id=None,
    pad_token_id: int = 0,
    length_penalty: float = 1.0,
    dtype=None,
    telemetry=None,
):
    """KV-cached beam search as ONE jitted decode loop.

    The reference reaches beam search by delegating to HF ``generate``
    (``deepspeed/inference/engine.py:578``), which re-orders its past-KV
    tuples on the host every step. Here beams are a device-side batch
    dimension: the prompt prefills ONCE at batch B, the cache is tiled to
    B*K rows before the loop (so the loop donates and aliases it in place),
    and each step's beam reorder is a gather over the cache's batch axis
    INSIDE the compiled ``lax.while_loop`` — no host round-trips until the
    final fetch.

    Hypothesis semantics follow HF's BeamSearchScorer with
    ``early_stopping=True``: each step draws 2K candidates so EOS landings
    never shrink the live set below K; EOS candidates are recorded into a
    per-row best-finished register scored by
    ``cum_logprob / (prompt_len + emitted)**length_penalty`` (full sequence
    length, the HF denominator) and the K best non-EOS candidates continue;
    a row stops once K finished hypotheses have been seen. The final answer
    is the better of the best finished hypothesis and the best live beam.
    First-expansion dedup: beam 0 starts at cum 0, the rest at -inf, so the
    first top-2K draw expands distinct tokens. Returns
    [B, prompt_len + emitted].
    """
    K = int(num_beams)
    tokens = jnp.asarray(input_ids)
    if tokens.ndim == 1:
        tokens = tokens[None, :]
    B, prompt_len = tokens.shape
    max_len = prompt_len + max_new_tokens
    V = cfg.vocab_size

    cache = init_cache(cfg, B, max_len, dtype=dtype)
    prefill, _ = build_decoder(cfg, telemetry)
    logits, cache = prefill(params, tokens, cache)  # [B, V]

    # tile to B*K OUTSIDE the loop: the loop's donated cache/out buffers are
    # then exactly the arrays it carries, so XLA aliases them in place
    # one-time beam tiling, not a per-step expansion: after divergence each
    # beam owns its cache rows (the loop updates them in place per beam)
    cache = KVCache(k=jnp.repeat(cache.k, K, axis=1), v=jnp.repeat(cache.v, K, axis=1))  # lint: allow(DS-R001)
    out0 = jnp.full((B * K, max_len), pad_token_id, tokens.dtype)
    out0 = jax.lax.dynamic_update_slice(out0, jnp.repeat(tokens, K, axis=0), (0, 0))
    logits = jnp.repeat(logits, K, axis=0)

    key = (
        "beam", _cfg_key(cfg), B, K, prompt_len, max_new_tokens,
        eos_token_id, int(pad_token_id), float(length_penalty),
        str(tokens.dtype), str(cache.k.dtype), _telemetry_uid(telemetry),
    )
    loop = _loop_cache_get(key)
    if loop is None:

        def _norm_score(cum, emitted):
            # HF denominator: the FULL sequence length (prompt + generated)
            length = (prompt_len + jnp.maximum(emitted, 1)).astype(jnp.float32)
            return cum / length**length_penalty

        def _loop(params, logits, cache, out):
            cum0 = jnp.full((B, K), NEG_INF_F, jnp.float32).at[:, 0].set(0.0)
            rows = jnp.arange(B, dtype=jnp.int32)

            def cond(c):
                step, done_count = c[0], c[5]
                live = (
                    jnp.any(done_count < K)
                    if eos_token_id is not None
                    else jnp.bool_(True)
                )
                return jnp.logical_and(step < max_new_tokens, live)

            def body(c):
                (step, logits, cache, out, cum, done_count,
                 best_score, best_out, best_len) = c
                # every live beam has emitted exactly `step` tokens (beams
                # only permute among equals), so length is scalar state
                logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
                total = cum[:, :, None] + logp.reshape(B, K, V)
                # 2K candidates (HF): EOS landings never starve the live set
                cand_cum, flat_idx = jax.lax.top_k(total.reshape(B, K * V), 2 * K)
                cand_beam = flat_idx // V  # [B, 2K]
                cand_tok = flat_idx % V

                if eos_token_id is not None:
                    is_eos = cand_tok == eos_token_id
                    # HF records/counts ONLY EOS candidates ranked < K
                    # (BeamSearchScorer: beam_token_rank >= group_size -> skip);
                    # lower-ranked EOS are neither recorded nor continued
                    topk_rank = jnp.arange(2 * K) < K
                    rec = is_eos & topk_rank[None, :]
                    fin = jnp.where(
                        rec, _norm_score(cand_cum, jnp.int32(step) + 1), NEG_INF_F
                    )
                    j = jnp.argmax(fin, axis=1)
                    row_score = jnp.take_along_axis(fin, j[:, None], 1)[:, 0]
                    src = rows * K + jnp.take_along_axis(cand_beam, j[:, None], 1)[:, 0]
                    cand_out = jnp.take(out, src, axis=0)
                    cand_out = jax.lax.dynamic_update_slice(
                        cand_out,
                        jnp.full((B, 1), eos_token_id, out.dtype),
                        (0, prompt_len + step),
                    )
                    better = row_score > best_score
                    best_out = jnp.where(better[:, None], cand_out, best_out)
                    best_score = jnp.where(better, row_score, best_score)
                    best_len = jnp.where(better, step + 1, best_len)
                    done_count = done_count + jnp.sum(rec, axis=1)
                    live_vals = jnp.where(is_eos, NEG_INF_F, cand_cum)
                else:
                    live_vals = cand_cum

                new_cum, pick = jax.lax.top_k(live_vals, K)  # [B, K] into 2K
                beam_src = jnp.take_along_axis(cand_beam, pick, axis=1)
                tok = jnp.take_along_axis(cand_tok, pick, axis=1).astype(out.dtype)

                flat_src = (beam_src + rows[:, None] * K).reshape(B * K)
                out = jnp.take(out, flat_src, axis=0)
                cache = KVCache(
                    k=jnp.take(cache.k, flat_src, axis=1),
                    v=jnp.take(cache.v, flat_src, axis=1),
                )

                flat_tok = tok.reshape(B * K)
                out = jax.lax.dynamic_update_slice(
                    out, flat_tok[:, None], (0, prompt_len + step)
                )
                logits, cache = _forward_with_cache(
                    cfg, params, flat_tok[:, None], cache, prompt_len + step
                )
                return (step + 1, logits, cache, out, new_cum, done_count,
                        best_score, best_out, best_len)

            state = (
                jnp.int32(0), logits, cache, out, cum0,
                jnp.zeros((B,), jnp.int32),              # finished hyps seen
                jnp.full((B,), NEG_INF_F, jnp.float32),  # best finished score
                out[::K],                                # best finished seq
                jnp.zeros((B,), jnp.int32),              # its emitted length
            )
            (step, _, cache, out, cum, _,
             best_score, best_out, best_len) = jax.lax.while_loop(cond, body, state)
            live = _norm_score(cum, step)  # every live beam emitted `step`
            k_live = jnp.argmax(live, axis=1)
            live_out = jnp.take(out, rows * K + k_live, axis=0)
            live_score = jnp.take_along_axis(live, k_live[:, None], 1)[:, 0]
            use_fin = best_score >= live_score
            final_out = jnp.where(use_fin[:, None], best_out, live_out)
            final_len = jnp.where(use_fin, best_len, step)
            return final_out, jnp.max(final_len), cache

        loop = _jit(_loop, telemetry, "kv_beam_loop", donate_argnums=(2, 3))
        _loop_cache_put(key, loop)

    out, n_emitted, _ = loop(params, logits, cache, out0)
    return out[:, : prompt_len + int(jax.device_get(n_emitted))]


# --- paged (block-table) serving programs ----------------------------------
# The continuous-batching scheduler (inference/scheduler.py) drives these.
# ONE `build_ragged_step` program per step handles mixed prefill-chunk,
# decode, and verify rows together, driven by per-row (kv_len, q_len)
# metadata arrays — total compiled serving programs ≤ 2 (a narrow
# decode/verify width plus the mixed width covering prefill chunks),
# whatever the traffic.


# one cache for every compiled serving program, keyed by the unified
# program name + the build inputs that change lowering
_paged_program_cache: Dict[Tuple, Any] = {}


def _paged_program_key(name, cfg, page_size, attn_impl, telemetry, tp=None) -> Tuple:
    return (
        name, _cfg_key(cfg), int(page_size), attn_impl, _telemetry_uid(telemetry),
        None if tp is None else tp.cache_key(),
    )


def ragged_program_name(rows: int, width: int, tp=None) -> str:
    """The ``compile_stats()`` key of ``build_ragged_step(cfg, rows, width,
    ..., tp=tp)`` and, with ``jit_`` in front, its XLA module's name: what
    the scheduler's ``serve.pack`` / ``serve.dispatch`` spans carry as
    ``program``. The ragged ≤2-compile gate and the benchmark's compile
    counters both count ``paged_*`` entries."""
    return f"paged_ragged_r{int(rows)}_w{int(width)}" + _tp_suffix(tp)


def _tp_suffix(tp) -> str:
    """Program-name suffix for tensor-parallel builds: a shard_map-wrapped
    program is a different executable from the single-chip one even at the
    same (rows, width), and telemetry must not merge their counters — so
    every knob that changes the compiled schedule (degree, quantized
    comms, int8 weights, non-default comm chunking) marks the name."""
    if tp is None:
        return ""
    return (
        f"_tp{tp.degree}"
        + ("q" if tp.quantized_allreduce else "")
        + ("w8" if tp.quantized_weights else "")
        + (f"c{tp.comm_chunks}" if tp.comm_chunks != 2 else "")
    )


def _accepted_prefix(tokens, greedy, n_drafts):
    """Per-row count of leading drafts (``tokens[:, 1:]``) that match the
    model's own greedy argmax for their positions, bounded by ``n_drafts``
    — THE acceptance rule (argmax-compare ⇒ greedy outputs byte-identical
    to sequential decode)."""
    n_slots = tokens.shape[1] - 1
    matches = (tokens[:, 1:] == greedy[:, :-1]) & (
        jnp.arange(n_slots, dtype=jnp.int32)[None, :] < n_drafts[:, None]
    )
    return jnp.sum(jnp.cumprod(matches.astype(jnp.int32), axis=1), axis=1)


_token_feed_cache: Dict[Any, Tuple[Any, Any]] = {}


def build_token_feed(tp=None) -> Tuple[Any, Any]:
    """``(next_tokens, feed_tokens)``: the two small programs that carry a
    row's next decode token from one ragged step's result to the next
    step's window without the host reading it, so that the scheduler can
    enqueue step n+1 before it fetches step n (``scheduler.py``).

    * ``next_tokens(out, cols [R]) -> [R]``: ``out[r, cols[r]]`` of the
      token rows (an MoE model's ``MOE_STAT_ROWS`` lie past them), enqueued
      behind the step that computes ``out`` with ``cols = q_lens``: the
      greedy token after each row's last live position.
    * ``feed_tokens(tokens [R, W], nxt [R], src [R]) -> [R, W]``: the host's
      window with ``tokens[i, 0]`` replaced by ``nxt[src[i]]`` wherever
      ``src[i] >= 0``, enqueued before the step that takes the window.

    The step programs' text and operands stay what they were. Each of the
    two compiles once a shape of ONE operand (``out``'s width, the window's
    width), so whatever runs both step programs has compiled all four.
    Under ``tp`` the window is placed replicated on the mesh whatever fed
    it, so the step program sees one signature from its first call on."""
    key = None if tp is None else tp.cache_key()
    pair = _token_feed_cache.get(key)
    if pair is not None:
        return pair

    def next_tokens(out, cols):
        return out[jnp.arange(cols.shape[0]), cols]

    def feed_tokens(tokens, nxt, src):
        fed = jnp.where(src >= 0, nxt[jnp.maximum(src, 0)], tokens[:, 0])
        return tokens.at[:, 0].set(fed)

    placed = {}
    if tp is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        placed = {"out_shardings": NamedSharding(tp.mesh, PartitionSpec())}
    pair = _token_feed_cache[key] = (jax.jit(next_tokens, **placed), jax.jit(feed_tokens, **placed))
    return pair


DENSE_TOKEN_TILE = 512
MOE_ROWS_PER_EXPERT = 128


def token_tile(cfg) -> int:
    """Packed tokens in one tile of the ragged step's token-wise work, from
    the layer's shapes alone; 0 for a model whose step is never tiled.

    A wide window (``rows x width`` slots above this number) is compacted and
    everything that is a function of one token runs tile by tile over the
    live tokens only (``_paged_layers``). Every tile streams the layer's
    weights again, so the tile is the smallest whose arithmetic hides that
    stream with room to spare: a v5e multiplies 197e12 / 819e9 = 240 rows in
    the time it takes to stream the matrix they meet, and at twice that, 512,
    a full window's four tiles cost what its slab does (+4%, measured:
    ``tools/mixed_step_bench.py``, PERF.md PR 30), where 256 rows neither hide
    the stream nor are hidden by it (a tile a fifth cheaper, a full window
    +11%). A dropless routed FFN spreads a tile's ``tile x k`` assignments
    over E experts, and an expert's matrices are streamed for however few rows
    reach it, so its tile is the one that brings each expert a whole row tile
    of the grouped matmul on average (``128 E / k``: 1,024 tokens for OLMoE's 8
    of 64; at 512 a full window reads every expert six times for the slab's
    three), never below the dense tile. A capacity-routed MoE
    (``moe_drop_tokens``) is not tiled: an expert's capacity is a function of
    the window's slot count, so a tile would drop other tokens than the window
    does. A model that holds a SHARE of the experts its router chooses from
    (``moe_router_experts`` above ``num_experts``) takes the dense tile: of a
    tile's ``tile x k`` assignments only the held share arrives, so the tile
    that would bring a held expert a whole row tile is that of the uncut layer
    (5,120 tokens for 8 of 320), 25 times the ~200 live tokens of a steady mixed
    step, whose dense work it would multiply: 1,746 tokens/s at 5,120, 2,505 at
    1,024, 2,707 at 512 (v5e, 40 of 320 experts held, PERF.md PR 31)."""
    if getattr(cfg, "num_experts", 0) and getattr(cfg, "moe_top_k", 0):
        if cfg.moe_drop_tokens:
            return 0
        if (getattr(cfg, "moe_router_experts", None) or cfg.num_experts) > cfg.num_experts:
            return DENSE_TOKEN_TILE
        per_expert = -(-MOE_ROWS_PER_EXPERT * cfg.num_experts // cfg.moe_top_k)
        return max(DENSE_TOKEN_TILE, -(-per_expert // DENSE_TOKEN_TILE) * DENSE_TOKEN_TILE)
    return DENSE_TOKEN_TILE


def token_tiles(cfg, rows: int, width: int, live_tokens: int) -> int:
    """Tiles the ``rows x width`` ragged program runs for ``live_tokens``
    live tokens: 0 where the window is at most one tile (such a program
    computes its whole slab), else ``ceil(live_tokens / token_tile(cfg))``.
    The scheduler's ``serve.pack`` counts with it."""
    tile = token_tile(cfg)
    if not tile or rows * width <= tile:
        return 0
    return -(-int(live_tokens) // tile)


def routed_rows(cfg, rows: int, width: int) -> int:
    """Tokens ONE call of a routed FFN takes in the ``rows x width`` ragged
    program: a token tile where the window is tiled, else its whole slab; 0
    for a model that routes nothing through ``moe/routed_ffn.py`` (no experts,
    or capacity routing). The engine records the assignment plan's form at
    these sizes where it builds the server (``moe.route_plan``)."""
    if not (getattr(cfg, "num_experts", 0) and getattr(cfg, "moe_top_k", 0)) or cfg.moe_drop_tokens:
        return 0
    tile = token_tile(cfg)
    return tile if tile and rows * width > tile else rows * width


class _Packed(NamedTuple):
    """A ragged window's live tokens, row after row, at the front of a
    buffer of whole tiles."""

    tile: int
    n_tiles: jax.Array  # int32 scalar: ceil(live tokens / tile)
    slot: jax.Array  # [NP] int32: packed index -> flat slab slot (any slot past the live tokens)
    index: jax.Array  # [B, T] int32: slab slot -> packed index (any index for a dead slot)
    live: jax.Array  # [NP] bool

    def tiles(self, body, init):
        """``body(start, carry) -> carry`` over the live tiles' first packed
        indices: the trip count is data, the body is traced once."""
        return jax.lax.fori_loop(
            0, self.n_tiles, lambda t, carry: body(jax.lax.mul(t, jnp.int32(self.tile)), carry), init
        )

    def take(self, packed, start):
        return jax.lax.dynamic_slice_in_dim(packed, start, self.tile, axis=0)

    def expand(self, packed):
        """[NP, ...] -> [B, T, ...]: every live slot's own packed row."""
        return jnp.take(packed, self.index, axis=0, mode="clip")


def _pack_window(q_lens, B: int, T: int, tile: int) -> _Packed:
    """From ``q_lens`` alone: slot (r, j), ``j < q_lens[r]``, is packed token
    ``cumsum(q_lens)[r] - q_lens[r] + j``."""
    n_packed = -(-B * T // tile) * tile
    q = jnp.asarray(q_lens, jnp.int32)
    ends = jnp.cumsum(q)
    starts = ends - q
    i = jnp.arange(n_packed, dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(i[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), B - 1)
    slot = jnp.clip(row * T + i - starts[row], 0, B * T - 1)
    index = jnp.minimum(starts[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :], n_packed - 1)
    return _Packed(tile, (ends[-1] + (tile - 1)) // tile, slot, index, i < ends[-1])


def _split_expert_stacks(cfg, layers):
    """A dropless MoE model's expert stacks stay out of the per-layer
    weights, like the pools: seen as one stack of L x E experts, the grouped
    matmul reaches its layer's through an offset, and nothing copies a
    layer's experts out of the stack first. Returns (layers without them,
    the stacks or None)."""
    if "moe" not in layers or cfg.moe_drop_tokens:
        return layers, None
    stacks = jax.tree_util.tree_map(lambda a: a.reshape((-1,) + a.shape[2:]), layers["moe"]["experts"])
    return {**layers, "moe": {k: v for k, v in layers["moe"].items() if k != "experts"}}, stacks


def _bound_moe_ffn(cfg, live, expert_stacks, layer):
    moe_ffn = functools.partial(_moe_ffn, live=live)
    if expert_stacks is not None:
        moe_ffn = functools.partial(moe_ffn, experts=expert_stacks, group_offset=layer * cfg.num_experts)
    return moe_ffn


def _paged_layers(cfg, params, tokens, k_pages, v_pages, page_table, positions_b, attn_impl,
                  *, prefill_kv_lens, ragged_q_lens, tp=None):
    """Embedding and layers of the ragged step (``_paged_forward`` has the
    contract). A window of at most one token tile (``token_tile``; a test of
    shapes: the width-1 program, a verify width) is
    computed as the ``[B, T]`` slab it is. A wider one is computed over its
    LIVE tokens only, inside the one program:

    * the live tokens and their positions are packed to the front of a
      ``[B T, H]`` buffer (``_pack_window``: a few int32 ops and one gather of
      the embedding);
    * whatever is a function of one token runs in ``fori_loop``s over
      ``ceil(live / tile)`` tiles of packed tokens, each body traced once at
      ``[1, tile, ...]``: norm, q/k/v projection, QK-norm and RoPE before the
      attention call; output projection, residuals and the MLP or the routed
      FFN after it. A tile without a live token is never run; the last tile's
      tail is masked as dead slots are (``live`` for the router, no page for
      k/v);
    * ``ragged_paged_attention`` keeps its ``[R, W, ...]`` windows and is
      called once a layer, outside the loops: the packed q, k, v are expanded
      to their slots by a gather (a live slot reads its own packed row; what a
      dead slot reads is never attended nor kept), and the post-attention loop
      gathers its tile's rows of the kernel's output.

    The per-layer weights are indexed out of their stacks INSIDE the tile
    bodies (the layer scan carries an index, not slices): a slice made in
    the scan's body would be copied to be handed to the inner loop, a layer's
    weights read and written once more a layer. In both forms a barrier
    stands between the projections' matmuls and the head split
    (``project``), so each matmul reads its matrix where the stack lies.
    A looped model (``num_loops > 1``) runs the layer scan once a PASS over
    the same weight stacks, inside the one program (scope ``loop_pass`` around
    a pass's layers, ``pass_norm`` around the final norm between two passes):
    the weights' index is the scan's own ``l``, the pools' index
    ``t * num_layers + l`` runs on through the pools' ``L = num_loops *
    num_layers`` cache layers (the slab form's carried counter; the packed
    form's offset a pass), so each pass writes and attends to its own keys and
    values and the whole pools ride through every pass's scan, never a pass's
    slice of them. The branch is Python's, taken when the step is built: a
    model with one pass traces the one scan it always did.
    Returns ``(x, new_k, new_v, moe_counts, packed)``: ``x`` is the slab
    ``[B, T, H]`` and ``packed`` None, or ``x`` is the packed ``[NP, H]`` and
    ``packed`` says where its rows belong."""
    from deepspeed_tpu.ops.transformer.paged_attention import ragged_paged_attention

    B, T = tokens.shape
    dtype = k_pages.dtype
    scale = _softmax_scale(cfg, cfg.head_dim)
    tile = token_tile(cfg)
    loops = getattr(cfg, "num_loops", 1)  # a Python branch where the step is built: one pass traces what it always did

    def embed(tokens, positions):
        x = params["embed"]["tokens"].astype(dtype)[tokens]
        if cfg.position == "learned":
            x = x + params["embed"]["pos"].astype(dtype)[positions]
        return x

    def project(p, x, positions):
        # the head split kept apart from the matmul, or the compiler folds it
        # in, wants the weights with the contracted dimension minor for it,
        # and copies them to that layout: a layer's once a layer in the slab
        # program, their whole STACK on the way into the tile loop
        q, k_new, v_new = jax.lax.optimization_barrier(_project_qkv(cfg, p, x))
        q, k_new, v_new = _split_heads(cfg, q, k_new, v_new)
        if cfg.position == "rope":
            q = _rope(q, positions, cfg.rope_theta, cfg.rope_dim)
            k_new = _rope(k_new, positions, cfg.rope_theta, cfg.rope_dim)
        return q, k_new, v_new

    def attend(q, k_new, v_new, kp, vp, layer):
        return ragged_paged_attention(
            q, k_new, v_new, kp, vp, layer, page_table, prefill_kv_lens,
            ragged_q_lens, scale=scale, impl=attn_impl,
        )

    # named scopes (here, in ``_post_attention``, ``_ffn_body`` and
    # ``_final_logits``) put the region into every op's name stack, where a
    # profiler trace reads it: ``attention``, ``mlp``, ``head_sample``.
    # Names only, nothing computed differently.
    if not tile or B * T <= tile:
        x = embed(tokens, positions_b)
        live = jnp.arange(T, dtype=jnp.int32)[None, :] < ragged_q_lens[:, None]
        layers, expert_stacks = _split_expert_stacks(cfg, params["layers"])

        def layer_step(carry, p):
            x, kp, vp, layer = carry
            with jax.named_scope("attention"):
                q, k_new, v_new = project(p, x, positions_b)
                attn, kp, vp = attend(q, k_new, v_new, kp, vp, layer)
            x, moe_counts = _post_attention(
                cfg, p, x, attn, tp=tp, moe_ffn=_bound_moe_ffn(cfg, live, expert_stacks, layer)
            )
            return (x, kp, vp, layer + 1), moe_counts

        carry = (x, k_pages, v_pages, jnp.int32(0))
        if loops == 1:
            (x, new_k, new_v, _), moe_counts = jax.lax.scan(layer_step, carry, layers)
            return x, new_k, new_v, moe_counts, None
        # a looped stack: the scan again over the SAME stacks a pass, the
        # carried index running on through the pools' loops x L cache layers
        x, new_k, new_v, _ = _looped_passes(cfg, params, carry, lambda t, carry: jax.lax.scan(layer_step, carry, layers)[0])
        return x, new_k, new_v, None, None

    packed = _pack_window(ragged_q_lens, B, T, tile)
    positions = jnp.take(positions_b.reshape(-1), packed.slot, mode="clip")
    x = embed(jnp.take(tokens.reshape(-1), packed.slot, mode="clip"), positions)
    layers, expert_stacks = _split_expert_stacks(cfg, params["layers"])
    NH, NKV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def layer_step(carry, layer, first_cache_layer=0):
        x, kp, vp = carry
        # pass t's layer l of a looped stack owns cache layer t * L + l
        cache_layer = layer + first_cache_layer if first_cache_layer else layer

        def weights(start):
            # tied to the tile, or the compiler hoists the slices out of the tile loop
            stacks, _ = jax.lax.optimization_barrier((layers, start))
            return jax.tree_util.tree_map(lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False), stacks)

        def before(start, qkv):
            q, k_new, v_new = project(
                weights(start), packed.take(x, start)[None], packed.take(positions, start)[None]
            )
            return tuple(
                jax.lax.dynamic_update_slice_in_dim(buf, new[0].astype(dtype).reshape(packed.tile, -1), start, axis=0)
                for buf, new in zip(qkv, (q, k_new, v_new))
            )

        with jax.named_scope("attention"):
            qkv = packed.tiles(before, tuple(jnp.zeros((x.shape[0], n * D), dtype) for n in (NH, NKV, NKV)))
            attn, kp, vp = attend(
                *(packed.expand(a).reshape(B, T, n, D) for a, n in zip(qkv, (NH, NKV, NKV))), kp, vp, cache_layer
            )
            attn = attn.reshape(B * T, NH, D)

        def after(start, carry):
            x, counts = carry
            with jax.named_scope("attention"):
                attn_tile = jnp.take(attn, packed.take(packed.slot, start), axis=0, mode="clip")[None]
            moe_ffn = _bound_moe_ffn(cfg, packed.take(packed.live, start)[None], expert_stacks, layer)
            x_tile, tile_counts = _post_attention(
                cfg, weights(start), packed.take(x, start)[None], attn_tile, tp=tp, moe_ffn=moe_ffn
            )
            x = jax.lax.dynamic_update_slice_in_dim(x, x_tile[0], start, axis=0)
            return x, (None if tile_counts is None else counts + tile_counts)

        x, moe_counts = packed.tiles(after, (x, jnp.zeros((cfg.num_experts,), jnp.int32) if "moe" in layers else None))
        return (x, kp, vp), moe_counts

    carry, steps = (x, k_pages, v_pages), jnp.arange(cfg.num_layers, dtype=jnp.int32)
    if loops == 1:
        (x, new_k, new_v), moe_counts = jax.lax.scan(layer_step, carry, steps)
        return x, new_k, new_v, moe_counts, packed
    x, new_k, new_v = _looped_passes(
        cfg, params, carry,
        lambda t, carry: jax.lax.scan(functools.partial(layer_step, first_cache_layer=t * cfg.num_layers), carry, steps)[0],
    )
    return x, new_k, new_v, None, packed


def _paged_forward(cfg, params, tokens, k_pages, v_pages, page_table, positions_b,
                   _unused, attn_impl, *, prefill_kv_lens, ragged_q_lens, tp=None):
    """Forward a ragged ``[B, T]`` window of tokens against the paged cache
    (mixed prefill/decode/verify rows, per-row metadata — the one-program
    serving step): each layer writes the window's k/v into its pages and
    attends in ONE call of the ragged entry. Row b's tokens sit at
    ``prefill_kv_lens[b] - ragged_q_lens[b] ..`` (``positions_b``, for the
    embeddings and RoPE), slots past ``ragged_q_lens[b]`` reach no live
    page, a row with ``ragged_q_lens[b] == 0`` is dead. ``_unused`` takes
    no part: ``benchmark/tools/olmoe_logits_check.py`` passes ``None`` in
    that place (ROADMAP Queue 3 item 11). ``tp`` (a
    ``inference/tp.py:TPServing``) marks the body as running INSIDE
    shard_map on a tensor-parallel mesh: ``cfg`` is then the local per-shard
    view (heads and kv pages sliced on the head axes), the row-parallel
    projections all-reduce through the context, and the returned logits may
    be the local vocab slice.

    The stacked pools ``[L, NP, NKV, P, D]`` (``L`` the model's CACHE layers,
    ``models/config.py::cache_layers``: as many as layers of weights but in a
    looped model, which keeps ``num_loops`` of them for every layer of
    weights) ride in the layer scan's CARRY beside the layer index, and every
    write and read reaches its layer through that index: the pools are never
    sliced into per-layer ``xs`` nor restacked from ``ys``, nor sliced a
    pass, so the donated buffers are the ones returned. On
    the Pallas path the fused kernel is the only operation applied to them
    (aliased in → out), which also leaves their layout to nobody but the
    kernel.
    Only the attention kernel sees every slot of the window: a window wider
    than one token tile has its token-wise work done over its live tokens
    only (``_paged_layers``), and this entry lays the result back on the
    slab for the head (a dead slot's logits are then some live token's: the
    caller ignores them). ``build_ragged_step`` takes its arg-max on the
    packed tiles instead.
    An MoE model routes only the window's live tokens and hands back its
    per-layer, per-expert assignment counts.
    Returns (logits [B, T, V], new_k_pages, new_v_pages, moe_counts [L, E]
    or None for a dense model)."""
    x, new_k, new_v, moe_counts, packed = _paged_layers(
        cfg, params, tokens, k_pages, v_pages, page_table, positions_b, attn_impl,
        prefill_kv_lens=prefill_kv_lens, ragged_q_lens=ragged_q_lens, tp=tp,
    )
    if packed is not None:
        x = packed.expand(x)
    return _final_logits(cfg, params, x), new_k, new_v, moe_counts


def _argmax(logits, tp):
    return tp.argmax(logits) if tp is not None else jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _packed_greedy(cfg, params, x, packed: _Packed, tp=None):
    """Final norm, head and arg-max of the packed ``x`` [NP, H], tile by live
    tile, laid back on the slab: ``[B, T]`` int32. ``[B, T, V]`` logits never
    exist; a dead slot reads any token."""

    def head(start, greedy):
        logits = _final_logits(cfg, params, packed.take(x, start)[None])
        with jax.named_scope("head_sample"):
            return jax.lax.dynamic_update_slice_in_dim(greedy, _argmax(logits, tp)[0], start, axis=0)

    return packed.expand(packed.tiles(head, jnp.zeros((x.shape[0],), jnp.int32)))


MOE_STAT_ROWS = 3


def _moe_stat_rows(moe_counts, width: int):
    """A step's routing counts, as rows for the packed result of an MoE
    model's ragged step: column 0 of three rows beneath the ``rows`` real
    ones holds the live (token, expert) assignments over all layers, the
    experts hit (with at least one live token; summed over layers) and the
    largest number of assignments any one expert of any layer received."""
    stats = jnp.stack([jnp.sum(moe_counts), jnp.sum(moe_counts > 0), jnp.max(moe_counts)]).astype(jnp.int32)
    return jnp.zeros((MOE_STAT_ROWS, width), jnp.int32).at[:, 0].set(stats)


def build_ragged_step(cfg, rows: int, width: int, page_size: int,
                      attn_impl: str = "auto", telemetry=None, tp=None):
    """THE one serving program: a ``rows × width`` ragged step that handles
    mixed prefill-chunk, decode, and verify rows in a single dispatch.

    ``ragged_step(params, tokens [R, W], k_pages, v_pages,
    page_table [R, MAXP], lengths [R], q_lens [R])
    -> (out [R, W+1], k_pages, v_pages)``.

    Row r carries ``q_lens[r]`` real tokens written at absolute positions
    ``lengths[r] + j`` (``lengths`` = the row's live kv length BEFORE the
    step — prefill progress and decode length coincide there). The mode is
    pure data, never shape:

    * a **prefill chunk** row is the next ``q_lens[r]`` prompt tokens;
    * a **decode** row is the single pending token (``q_lens[r] == 1``);
    * a **verify** row is the pending token plus ``q_lens[r] - 1`` drafts;
    * a **dead** padding row has ``q_lens[r] == 0`` (sentinel table,
      trash-page writes, zero attention).

    The program writes k/v for every real position (window slots past
    ``q_lens[r]`` reach no live page) and attends in ONE ragged
    paged-attention call a layer, driven by the per-row ``(kv_len, q_len)``
    metadata, and resolves every mode in-program: ``out[r, 1 + j]`` is the
    greedy token after position j (decode rows read ``out[r, 1]``, a
    finishing prefill chunk reads ``out[r, q_lens[r]]``), and ``out[r, 0]``
    is the verify rows' accepted-prefix length (count of leading drafts
    matching the model's own greedy argmax — byte-identical to sequential
    decode; 0 wherever nothing was drafted). Pages are donated; the packed
    [R, W+1] fetch is the step's only host traffic. An MoE model's result has
    ``MOE_STAT_ROWS`` more rows, whose column 0 holds the step's routing
    counts (``_moe_stat_rows``): the same one fetch.

    Because slot count, chunk progress, spec-K, and the mode mix all ride
    in as array contents, shifting traffic NEVER retraces: the scheduler
    compiles at most two widths of this program (decode/verify width and
    the mixed width covering prefill chunks) for an entire serve.

    ``width`` sets the window the kernel sees, not the step's cost: a window
    wider than one token tile (``token_tile``) has everything but attention
    computed over its live tokens only, in tiles whose count is data
    (``_paged_layers``), the arg-max taken on the packed tiles
    (``_packed_greedy``) and 2,048 int32 laid back on the slab: ``[R, W, V]``
    logits never exist. A narrower window is computed as the slab it is.

    With ``tp`` (a ``inference/tp.py:TPServing``) the SAME body runs under
    ``shard_map`` on the tensor-parallel mesh: weights and kv pages ride
    in sharded (column/row-parallel projections, kv-head-sliced pools),
    the per-layer row-parallel all-reduces are explicit (chunked for
    overlap, optionally EQuARX-quantized), and the greedy/accepted-prefix
    resolution uses the global argmax — so the packed host fetch, the
    one-dispatch-per-step contract, the page donation, and the ≤2-program
    budget are all unchanged on the mesh.
    """
    if cfg.position == "alibi":
        raise NotImplementedError("paged serving does not support alibi attention biases")
    if rows < 1 or width < 1:
        raise ValueError(f"ragged step needs rows >= 1 and width >= 1, got {rows}x{width}")
    name = ragged_program_name(rows, width, tp)
    key = _paged_program_key(name, cfg, page_size, attn_impl, telemetry, tp)
    fn = _paged_program_cache.get(key)
    if fn is not None:
        return fn
    W = int(width)
    if getattr(cfg, "layer_types", None):
        # layers of more than one kind: another program, with the state store's
        # buffers beside the pages. Chosen here, when the program is built: a
        # uniform model's step below is traced as it always was
        if tp is not None:
            raise NotImplementedError(
                f"tensor-parallel serving of a hybrid (multi-kind) layer stack is not supported: layer_types names {sorted(set(cfg.layer_types))}, "
                "and nothing shards a kind's heads with its own pool (pages, rings, latents, an indexer's keys, a slot's state)"
            )
        from deepspeed_tpu.inference.hybrid_decode import build_hybrid_ragged_step

        return build_hybrid_ragged_step(cfg, rows, W, page_size, attn_impl, telemetry, name, key)
    run_cfg = cfg if tp is None else tp.local_cfg(cfg)

    def _step(params, tokens, k_pages, v_pages, page_table, lengths, q_lens):
        offs = jnp.arange(W, dtype=jnp.int32)
        positions_b = lengths[:, None] + offs[None, :]
        kv_lens = jnp.where(q_lens > 0, lengths + q_lens, 0)
        x, new_k, new_v, moe_counts, tiles = _paged_layers(
            run_cfg, params, tokens, k_pages, v_pages, page_table, positions_b,
            attn_impl, prefill_kv_lens=kv_lens, ragged_q_lens=q_lens, tp=tp,
        )
        if tiles is None:
            logits = _final_logits(run_cfg, params, x)
            with jax.named_scope("head_sample"):
                greedy = _argmax(logits, tp)  # [R, W]
        else:
            greedy = _packed_greedy(run_cfg, params, x, tiles, tp)
        with jax.named_scope("head_sample"):
            # verify resolution (inert elsewhere: decode rows have no drafts and
            # prefill rows' accepted count is ignored by the host)
            accepted = _accepted_prefix(tokens, greedy, q_lens - 1)
            packed = jnp.concatenate([accepted[:, None].astype(jnp.int32), greedy], axis=1)
            if moe_counts is not None:
                packed = jnp.concatenate([packed, _moe_stat_rows(moe_counts, W + 1)], axis=0)
        return packed, new_k, new_v

    body = _step if tp is None else tp.shard_program(_step)
    fn = _jit(body, telemetry, name, donate_argnums=(2, 3))
    _paged_program_cache[key] = fn
    return fn
