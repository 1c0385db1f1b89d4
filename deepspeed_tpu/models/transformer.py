"""Decoder-only transformer (the built-in model family).

TPU-native replacement for the reference's fused transformer layers
(``csrc/transformer/ds_transformer_cuda.cpp``,
``deepspeed/ops/transformer/transformer.py:296`` DeepSpeedTransformerLayer)
and the per-arch injected models (``deepspeed/model_implementations/``):
one configurable decoder covering GPT-2/Llama/OPT/NeoX-style architectures.

Engineering choices for the MXU/HBM:

* params for all layers are **stacked** ([L, ...] leading dim) and the block
  runs under ``lax.scan`` — O(1) compile time in depth, and XLA pipelines the
  per-layer collectives.
* ``jax.checkpoint`` (remat) wraps the scanned body with a configurable
  policy — the activation-checkpointing subsystem of the reference
  (``deepspeed/runtime/activation_checkpointing``).
* attention is the Pallas flash-attention kernel under
  ``config.flash_attention`` (causal, no alibi, no attention dropout) and
  einsum-based (MXU-shaped) otherwise.
* weights carry Megatron-style TP specs over the ``model`` axis
  (``tp_partition_rules``), composed with ZeRO sharding by the partitioner.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.config import TransformerConfig, refuse_looped
from deepspeed_tpu.runtime.module import DSModule

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float16": jnp.float16}


def _maybe_quantize_activation(x, site: str):
    """QAT activation hook (compression/act_quant.py contract): identity
    unless the enclosing forward was entered through a ``CompressedModule``
    with an active ``activation_quantization`` group. Lazy import keeps the
    model family free of the compression package on the hot path."""
    from deepspeed_tpu.compression.act_quant import is_active, maybe_quantize

    if not is_active():
        return x
    return maybe_quantize(x, site)


def _dense(x, p, key: str):
    """``x @ p[key]``, one of a layer's matrices. Under an active comm-overlap
    plan (``runtime/zero/overlap.py``, training's traces) the matmul goes
    through the plan, which may own the weight gradient's cross-batch sum
    (``OverlapPlan.matmul``); the value is the same. A quantized leaf
    (``compression/int8.py``) keeps its fused dequantization."""
    from deepspeed_tpu.compression.int8 import qmatmul
    from deepspeed_tpu.runtime.zero.overlap import active_plan

    plan = active_plan()
    if plan is None or not hasattr(p[key], "astype"):
        return qmatmul(x, p[key])
    return plan.matmul(x, p[key].astype(x.dtype), key)


def _norm(x, scale, bias, kind: str, eps: float):
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        rms = jnp.sqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
        out = x32 / rms * scale.astype(jnp.float32)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        out = (x32 - mean) / jnp.sqrt(var + eps) * scale.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def _rope(x, positions, theta: float, rope_dim=None):
    """Rotary embedding over the last dim of [B, T, N, D]. ``rope_dim``
    rotates only the leading features (GPT-J rotary_dim / NeoX rotary_pct);
    the tail passes through unrotated."""
    if rope_dim is not None and rope_dim < x.shape[-1]:
        rotated = _rope(x[..., :rope_dim], positions, theta)
        return jnp.concatenate([rotated, x[..., rope_dim:]], axis=-1)
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B,T,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _alibi_slopes(n_heads: int) -> np.ndarray:
    def pow2slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))

    if np.log2(n_heads).is_integer():
        return pow2slopes(n_heads)
    closest = 2 ** int(np.floor(np.log2(n_heads)))
    return np.concatenate([pow2slopes(closest), pow2slopes(2 * closest)[0::2][: n_heads - closest]])


def _vocab_sharded() -> bool:
    """True when the active topology tensor-shards the vocab dim (TP)."""
    try:
        from deepspeed_tpu.parallel.mesh import get_topology

        return get_topology().axis_size("model") > 1
    except Exception:
        return False


def cross_entropy_loss(logits, labels, ignore_index: int = -100):
    """Mean token CE in fp32, ignoring ``ignore_index`` positions.

    Two gold-logit strategies, picked at trace time:

    * TP (vocab-sharded logits): one-hot select — ``take_along_axis``'s
      transpose is a scatter-add whose sharding the SPMD partitioner cannot
      reconcile with vocab-sharded logits (involuntary full
      rematerialization); the select's transpose is a plain masked multiply.
    * otherwise: ``take_along_axis`` — the select costs a full extra
      HBM pass over the [tokens, vocab] logits (the widest tensor in the
      step) where the gather reads one element per token. Measured ~2% of
      the 125M-config step time on v5e.

    The fp32 cast happens inside each consumer (not once up front) so XLA
    fuses it into the logsumexp reduction instead of materializing an fp32
    copy of the logits."""
    mask = labels != ignore_index
    safe_labels = jnp.where(mask, labels, 0)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    if _vocab_sharded():
        vocab_iota = jnp.arange(logits.shape[-1], dtype=safe_labels.dtype)
        onehot = safe_labels[..., None] == vocab_iota
        gold = jnp.sum(jnp.where(onehot, logits.astype(jnp.float32), 0.0), axis=-1)
    else:
        gold = jnp.take_along_axis(logits, safe_labels[..., None], axis=-1)[
            ..., 0
        ].astype(jnp.float32)
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1)


class TransformerLM(DSModule):
    """Causal LM. Batch forms accepted by ``apply``:

    * ``tokens`` [B, T] — returns logits (inference path)
    * ``(tokens, labels)`` or ``{"input_ids":..., "labels":...}`` — returns
      the scalar LM loss (training path)
    """

    def __init__(self, config: TransformerConfig):
        self.config = config
        self.dtype = _DTYPES[config.dtype]

    # --- parameter construction ----------------------------------------
    def init(self, rng, batch) -> Dict[str, Any]:
        cfg = self.config
        H, L = cfg.hidden_size, cfg.num_layers
        NH, NKV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        I = cfg.intermediate_size
        keys = jax.random.split(rng, 16)
        k = iter(keys)
        std = 0.02

        def dense(key, shape, out_std=std):
            return (jax.random.normal(key, shape, dtype=jnp.float32) * out_std)

        def stacked(key, shape, out_std=std):
            return dense(key, (L,) + shape, out_std)

        params: Dict[str, Any] = {
            "embed": {"tokens": dense(next(k), (cfg.vocab_size, H))},
        }
        if cfg.position == "learned":
            params["embed"]["pos"] = dense(next(k), (cfg.max_seq_len, H))
        if cfg.embed_norm:
            params["embed"]["norm_scale"] = jnp.ones((H,))
            if cfg.norm == "layernorm":
                params["embed"]["norm_bias"] = jnp.zeros((H,))

        layer: Dict[str, Any] = {
            "attn_norm_scale": jnp.ones((L, H)),
            "wq": stacked(next(k), (H, NH * D)),
            "wk": stacked(next(k), (H, NKV * D)),
            "wv": stacked(next(k), (H, NKV * D)),
            "wo": stacked(next(k), (NH * D, H), out_std=std / np.sqrt(2 * L)),
            "mlp_norm_scale": jnp.ones((L, H)),
            "w_out": stacked(next(k), (I, H), out_std=std / np.sqrt(2 * L)),
        }
        if cfg.activation in ("swiglu", "geglu"):
            layer["w_gate"] = stacked(next(k), (H, I))
            layer["w_up"] = stacked(next(k), (H, I))
        else:
            layer["w_in"] = stacked(next(k), (H, I))
        if cfg.norm == "layernorm":
            layer["attn_norm_bias"] = jnp.zeros((L, H))
            layer["mlp_norm_bias"] = jnp.zeros((L, H))
        if cfg.qk_norm == "projection":
            layer["q_norm_scale"] = jnp.ones((L, NH * D))
            layer["k_norm_scale"] = jnp.ones((L, NKV * D))
        if cfg.qkv_bias:
            layer["bq"] = jnp.zeros((L, NH * D))
            layer["bk"] = jnp.zeros((L, NKV * D))
            layer["bv"] = jnp.zeros((L, NKV * D))
        if cfg.use_bias:
            layer["bo"] = jnp.zeros((L, H))
            layer["b_out"] = jnp.zeros((L, H))
            if cfg.activation not in ("swiglu", "geglu"):
                layer["b_in"] = jnp.zeros((L, I))
        if cfg.post_sublayer_norm:
            for name in ("attn_post_norm", "mlp_post_norm"):
                layer[name + "_scale"] = jnp.ones((L, H))
                if cfg.norm == "layernorm":
                    layer[name + "_bias"] = jnp.zeros((L, H))
        params["layers"] = layer

        if cfg.prenorm:  # post-LN nets end inside the last layer's norm
            params["final_norm_scale"] = jnp.ones((H,))
            if cfg.norm == "layernorm":
                params["final_norm_bias"] = jnp.zeros((H,))
        if not cfg.tie_embeddings:
            params["lm_head"] = dense(next(k), (H, cfg.vocab_size))
            if cfg.lm_head_bias:
                params["lm_head_bias"] = jnp.zeros((cfg.vocab_size,))
        if cfg.exit_gate:
            params["exit_gate"] = {"w": dense(next(k), (H,)), "b": jnp.zeros(())}
        return params

    # --- TP sharding rules ----------------------------------------------
    def tp_partition_rules(self, params_shapes=None) -> Any:
        """Megatron-style specs over the 'model' mesh axis: column-parallel
        qkv/gate/up (shard the output features = heads), row-parallel
        wo/w_out (shard the input features); vocab-parallel embeddings.
        The stacked layer dim [L] stays unsharded (it is scanned).
        (reference analog: deepspeed/module_inject/auto_tp.py policy walk)

        NOTE: the paged SERVING engine uses its own specialisation of this
        map (``inference/tp.py:TPServing.partition_specs``): same
        column/row split for the projections, but embeddings REPLICATE
        (the lookup gather stays chip-local under shard_map) and the
        untied LM head is vocab-COLUMN-parallel with an in-program global
        argmax instead of the input-vocab-sharded table here — serving
        resolves greedy tokens, never a cross-entropy."""
        if params_shapes is None:
            return None

        def spec_for(path: str, ndim: int) -> P:
            stacked = ndim == 3  # [L, in, out]
            col = {"wq", "wk", "wv", "w_gate", "w_up", "w_in"}
            row = {"wo", "w_out"}
            name = path.split("/")[-1]
            if name in col:
                return P(None, None, "model") if stacked else P(None, "model")
            if name in row:
                return P(None, "model", None) if stacked else P("model", None)
            if name in {"bq", "bk", "bv", "b_in"}:
                return P(None, "model") if ndim == 2 else P("model")
            if name == "tokens":
                return P("model", None)  # vocab-parallel embedding
            if name == "lm_head":
                return P(None, "model")
            return P(*([None] * ndim))

        def walk(prefix, tree):
            if isinstance(tree, dict):
                return {k: walk(f"{prefix}/{k}", v) for k, v in tree.items()}
            return spec_for(prefix, len(tree.shape))

        return walk("", params_shapes)

    # --- forward ---------------------------------------------------------
    def _attention(self, q, k, v, positions, dropout_rng, train):
        """[B, T, NH, D] q / [B, T, NKV, D] k,v → [B, T, NH, D].

        Dispatches to sequence-parallel paths BEFORE expanding GQA kv heads
        so ring's ppermute and (when divisible) Ulysses' all-to-all move only
        the NKV-head kv bytes.
        """
        cfg = self.config
        scale = (
            cfg.attn_softmax_scale
            if cfg.attn_softmax_scale is not None
            else 1.0 / np.sqrt(q.shape[-1])
        )
        if cfg.sequence_parallel:
            sp_out = self._sp_attention(q, k, v, positions, dropout_rng, train, scale)
            if sp_out is not None:
                return sp_out
        return self._local_full_attention(q, k, v, positions, scale, dropout_rng, train)

    def _local_full_attention(self, q, k, v, positions, scale, dropout_rng=None, train=False):
        """Full-sequence attention on (possibly head-sharded) q/k/v: the
        single implementation used by the local path and as the Ulysses
        local op. GQA (NKV < NH) is computed by grouping the queries against
        the shared kv rows — an NH-wide ``jnp.repeat`` of k/v here would
        materialize a G-times copy of the [B, S, NKV, D] activations every
        layer (the same blowup the paged decode path banned in PR 2); only
        the fused flash kernel, which requires equal head counts, still
        expands."""
        cfg = self.config
        NH, NKV = q.shape[2], k.shape[2]
        if (
            cfg.flash_attention
            and cfg.position != "alibi"
            and cfg.causal
            and (not train or cfg.attn_dropout == 0)  # no dropout inside the fused kernel
        ):
            if NKV != NH:
                k, v = _expand_gqa(q, k, v)  # kernel contract: equal head counts
            return _flash_on_mesh(q, k, v, scale)
        if NKV != NH:
            # grouped GQA: heads stay [NKV, G]-factored through both einsums
            B, T, _, D = q.shape
            G = NH // NKV
            qg = q.reshape(B, T, NKV, G, D)
            scores = jnp.einsum("btkgd,bskd->bkgts", qg, k).astype(jnp.float32) * scale
            if cfg.position == "alibi":
                slopes = jnp.asarray(_alibi_slopes(NH), dtype=jnp.float32).reshape(NKV, G)
                dist = (positions[:, None, :] - positions[:, :, None]).astype(jnp.float32)
                scores = scores - slopes[None, :, :, None, None] * jnp.abs(dist)[:, None, None]
            if cfg.causal:
                mask = positions[:, None, None, :, None] >= positions[:, None, None, None, :]
                scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            if train and cfg.attn_dropout > 0 and dropout_rng is not None:
                keep = jax.random.bernoulli(dropout_rng, 1 - cfg.attn_dropout, probs.shape)
                probs = probs * keep / (1 - cfg.attn_dropout)
            probs = probs.astype(v.dtype)
            out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
            return out.reshape(B, T, NH, D)
        scores = jnp.einsum("btnd,bsnd->bnts", q, k).astype(jnp.float32) * scale
        if cfg.position == "alibi":
            slopes = jnp.asarray(_alibi_slopes(NH), dtype=jnp.float32)
            dist = (positions[:, None, :] - positions[:, :, None]).astype(jnp.float32)
            scores = scores - slopes[None, :, None, None] * jnp.abs(dist)[:, None]
        if cfg.causal:
            mask = positions[:, None, :, None] >= positions[:, None, None, :]
            scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        if train and cfg.attn_dropout > 0 and dropout_rng is not None:
            keep = jax.random.bernoulli(dropout_rng, 1 - cfg.attn_dropout, probs.shape)
            probs = probs * keep / (1 - cfg.attn_dropout)
        probs = probs.astype(v.dtype)
        return jnp.einsum("bnts,bsnd->btnd", probs, v)

    def _sp_attention(self, q, k, v, positions, dropout_rng, train, scale):
        """Sequence-parallel attention (Ulysses all-to-all or ring ppermute).

        Returns None when the mesh has no sequence axis (caller falls through
        to the local path). Reference: deepspeed/sequence/layer.py (Ulysses);
        ring is the TPU-native long-context extension (sequence/ring.py).
        Both SP paths assume contiguous 0..T-1 positions (what ``_forward``
        produces); packed/offset position ids are not supported under SP.
        """
        cfg = self.config
        if cfg.sequence_parallel_mode not in ("ulysses", "ring"):
            raise ValueError(
                f"unknown sequence_parallel_mode {cfg.sequence_parallel_mode!r}; "
                "expected 'ulysses' or 'ring'"
            )
        from deepspeed_tpu.parallel.mesh import get_topology

        topo = get_topology()
        sp = topo.axis_size("sequence")
        if sp == 1:
            return None
        if cfg.position == "alibi":
            raise NotImplementedError("sequence_parallel with alibi positions is unsupported")
        if train and cfg.attn_dropout > 0:
            raise NotImplementedError("sequence_parallel with attention dropout is unsupported")
        batch_axes = topo.dense_batch_axes()
        head_axes = "model" if topo.axis_size("model") > 1 else None

        if cfg.sequence_parallel_mode == "ring":
            from deepspeed_tpu.sequence.ring import ring_attention

            return ring_attention(
                q, k, v,
                mesh=topo.mesh,
                causal=cfg.causal,
                scale=scale,
                batch_axes=batch_axes,
                head_axes=head_axes,
            )

        from deepspeed_tpu.sequence.layer import DistributedAttention

        # Ulysses scatters the head dim over the sequence axis; kv can ride
        # the all-to-all at NKV heads iff sp divides NKV — otherwise they
        # must be pre-expanded to NH (layer.py:37's head-count constraint).
        NKV = k.shape[2]
        expand_late = NKV != q.shape[2] and NKV % sp == 0

        def local_attn(q_, k_, v_):
            # grouped-GQA local op: the group ratio survives the head
            # scatter (NH/sp vs NKV/sp), so no expansion is needed here
            return self._local_full_attention(q_, k_, v_, positions, scale)

        dist_attn = DistributedAttention(
            local_attn, topo.mesh, batch_axes=batch_axes, head_axes=head_axes
        )
        if not expand_late:
            k, v = _expand_gqa(q, k, v)  # a2a head-count constraint: sp ∤ NKV
        return dist_attn(q, k, v)

    def _mlp(self, p, h, rng, train):
        """Dense FFN; MoE model families override this (returns (out, aux_loss))."""
        from deepspeed_tpu.moe.experts import apply_dense_ffn

        h = _maybe_quantize_activation(h, "layers/mlp_input")
        return apply_dense_ffn(p, h, self.config.activation, matmul=_dense), jnp.zeros((), jnp.float32)

    def _layer_params(self, params, i: int):
        """Per-layer param tree for the unrolled (non-scan) path; model
        families with heterogeneous layers (MoE interleave) override this."""
        return jax.tree_util.tree_map(lambda a: a[i], params["layers"])

    def _layer(self, carry_x, layer_params, positions, rng, train):
        cfg = self.config
        p = layer_params
        x = carry_x
        B, T, H = x.shape
        NH, NKV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

        # the two blocks are named scopes (``attention``, ``mlp``, inside the
        # caller's ``layers``): names on the ops for a profiler trace to
        # read, nothing computed differently
        with jax.named_scope("attention"):
            # pre-LN (GPT/Llama): norm feeds the block, residual stays unnormed.
            # post-LN (BERT family): the block reads the residual stream raw and
            # the norm is applied AFTER adding the residual.
            if cfg.prenorm:
                h = _norm(x, p["attn_norm_scale"], p.get("attn_norm_bias"), cfg.norm, cfg.norm_eps)
            else:
                h = x
            h = _maybe_quantize_activation(h, "layers/attn_input")
            q, k, v = (_dense(h, p, key) for key in ("wq", "wk", "wv"))
            if cfg.qkv_bias:
                q, k, v = q + p["bq"].astype(h.dtype), k + p["bk"].astype(h.dtype), v + p["bv"].astype(h.dtype)
            if cfg.qk_norm == "projection":
                q = _norm(q, p["q_norm_scale"], None, "rmsnorm", cfg.norm_eps)
                k = _norm(k, p["k_norm_scale"], None, "rmsnorm", cfg.norm_eps)
            q = q.reshape(B, T, NH, D)
            k = k.reshape(B, T, NKV, D)
            v = v.reshape(B, T, NKV, D)
            if cfg.position == "rope":
                q = _rope(q, positions, cfg.rope_theta, cfg.rope_dim)
                k = _rope(k, positions, cfg.rope_theta, cfg.rope_dim)
            rng, r_attn, r_hid, r_mlp = jax.random.split(rng, 4) if rng is not None else (None, None, None, None)
            attn = self._attention(q, k, v, positions, r_attn, train)
            attn = _dense(attn.reshape(B, T, NH * D), p, "wo")
            if cfg.use_bias:
                attn = attn + p["bo"].astype(h.dtype)
            if train and cfg.hidden_dropout > 0 and r_hid is not None:
                keep = jax.random.bernoulli(r_hid, 1 - cfg.hidden_dropout, attn.shape)
                attn = attn * keep / (1 - cfg.hidden_dropout)
            if cfg.post_sublayer_norm:
                attn = _norm(attn, p["attn_post_norm_scale"], p.get("attn_post_norm_bias"), cfg.norm, cfg.norm_eps)
        if cfg.parallel_residual:
            # GPT-J/NeoX: both branches read x — attn already consumed
            # norm1(x) as h; the mlp branch reads the SAME h (GPT-J shared
            # ln_1) or its own norm2(x) (NeoX)
            h_mlp = (
                h
                if cfg.shared_parallel_norm
                else _norm(x, p["mlp_norm_scale"], p.get("mlp_norm_bias"), cfg.norm, cfg.norm_eps)
            )
            with jax.named_scope("mlp"):
                out, aux = self._mlp(p, h_mlp, r_mlp, train)
            return x + attn + out, aux
        with jax.named_scope("mlp"):
            if cfg.prenorm:
                x = x + attn
                h = _norm(x, p["mlp_norm_scale"], p.get("mlp_norm_bias"), cfg.norm, cfg.norm_eps)
            else:
                x = _norm(x + attn, p["attn_norm_scale"], p.get("attn_norm_bias"), cfg.norm, cfg.norm_eps)
                h = x
            out, aux = self._mlp(p, h, r_mlp, train)
            if cfg.post_sublayer_norm:
                out = _norm(out, p["mlp_post_norm_scale"], p.get("mlp_post_norm_bias"), cfg.norm, cfg.norm_eps)
            if cfg.prenorm:
                return x + out, aux
            return _norm(x + out, p["mlp_norm_scale"], p.get("mlp_norm_bias"), cfg.norm, cfg.norm_eps), aux

    def _activation_constraint(self, x):
        """Pin [B, T, H] activations to (batch-axes, sequence, None): one
        explicit anchor stops XLA's sharding propagation from flip-flopping
        layouts at the embed→scan and scan→head boundaries ("involuntary
        full rematerialization" replicate-then-reshard). H stays replicated
        over 'model' — Megatron semantics: activations are full between
        blocks, sharded only inside them."""
        try:
            from deepspeed_tpu.parallel.mesh import get_topology

            topo = get_topology()
        except Exception:
            return x
        from jax.sharding import NamedSharding

        batch_axes = topo.dense_batch_axes()
        # pin T over 'sequence' only for SP models: a non-SP model's attention
        # needs the full sequence, and a T pin would force a replicate-reshard
        # around every attention block
        seq = (
            "sequence"
            if self.config.sequence_parallel and topo.axis_size("sequence") > 1
            else None
        )
        if batch_axes is None and seq is None:
            return x
        # standalone model.apply (no engine placed the batch): skip when the
        # shapes don't tile the mesh rather than demand engine batch sizes
        axes = batch_axes if isinstance(batch_axes, tuple) else (batch_axes,) if batch_axes else ()
        b_tile = int(np.prod([topo.axis_size(a) for a in axes])) if axes else 1
        s_tile = topo.axis_size("sequence") if seq else 1
        if x.shape[0] % b_tile or x.shape[1] % s_tile:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(topo.mesh, P(batch_axes, seq, None))
        )

    def _sparse_embed(self, params, tokens):
        """Token-embedding lookup whose backward DP-reduces compact
        (ids, rows) pairs (``runtime/sparse_tensor.py``; reference
        engine.py:2398-2465 sparse allreduce)."""
        from deepspeed_tpu.runtime.sparse_tensor import sparse_embedding_lookup

        data_axes = None
        try:
            from deepspeed_tpu.parallel.mesh import get_topology

            topo = get_topology()
            if topo.axis_size("sequence") > 1:
                raise ValueError(
                    "sparse_embedding_grads is unsupported with sequence "
                    "parallelism (the pair gather assumes batch-only sharding)"
                )
            axes = topo.dense_batch_axes()
            if axes is not None:
                data_axes = axes if isinstance(axes, tuple) else (axes,)
        except ValueError:
            raise
        except Exception:
            data_axes = None
        table = params["embed"]["tokens"].astype(self.dtype)
        return sparse_embedding_lookup(table, tokens, data_axes)

    def _forward(self, params, tokens, rngs, train, pld_theta=None, ltd_idx=None, exit_distribution=False):
        cfg = self.config
        if exit_distribution and not cfg.exit_gate:
            raise ValueError("exit_distribution needs a model with an exit gate (exit_gate=True, num_loops > 1)")
        tokens = jnp.asarray(tokens)
        B, T = tokens.shape
        # the step's regions are named scopes (``embed``, ``layers`` with
        # ``attention`` / ``mlp`` inside, ``head_loss``): the name lands in
        # every op's name stack, forward and backward, where a profiler trace
        # reads it (``benchmark/op_scopes.py``)
        with jax.named_scope("embed"):
            if cfg.sparse_embedding_grads:
                x = self._sparse_embed(params, tokens)
            else:
                x = params["embed"]["tokens"].astype(self.dtype)[tokens]
            positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
            if cfg.position == "learned":
                x = x + params["embed"]["pos"].astype(self.dtype)[positions[0]][None]
            if cfg.embed_norm:
                x = _norm(
                    x,
                    params["embed"]["norm_scale"],
                    params["embed"].get("norm_bias"),
                    cfg.norm,
                    cfg.norm_eps,
                )
            x = self._activation_constraint(x)

        base_rng = (rngs or {}).get("dropout") if isinstance(rngs, dict) else rngs
        L = cfg.num_layers
        pld_active = pld_theta is not None and train
        ltd_active = ltd_idx is not None and train
        if pld_active and base_rng is None:
            raise ValueError(
                "progressive layer drop needs a dropout rng (the per-layer "
                "keep draw); pass rngs={'dropout': key} to apply()"
            )
        if pld_active and ltd_active:
            raise ValueError(
                "progressive_layer_drop and random-LTD cannot be combined"
            )
        if pld_active or ltd_active:
            refuse_looped(
                cfg, "progressive layer drop / random-LTD",
                "their schedules count a layer of weights once a token (a keep probability by depth, the first and last layer always full)",
            )
        if ltd_active:
            n_ltd = int(ltd_idx.shape[0])
            if n_ltd > L - 2:
                raise ValueError(
                    f"random-LTD covers {n_ltd} layers but only {L - 2} middle "
                    "layers exist (the first and last layers always run full)"
                )

        # comm-overlap plan (runtime/zero/overlap.py): set by the engine
        # around its training-loss traces. reduce_grads forces each layer's
        # gradient reduction inside the backward scan, every leaf where it
        # lies; the prefetch pipeline below restructures the whole scan.
        # Both are value-preserving, so every path stays bit-identical to
        # the unpipelined program.
        from deepspeed_tpu.runtime.zero.overlap import active_plan

        overlap_plan = active_plan()

        def body(carry, scanned):
            x, rng = carry
            per_layer, layer_idx = scanned if pld_active else (scanned, None)
            if overlap_plan is not None:
                per_layer = overlap_plan.reduce_grads(per_layer)
            if not pld_active:
                x_new, rng, aux = self._scan_layer_step(
                    x, per_layer, positions, rng, train
                )
                return (x_new, rng), aux
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None

            def run(x_in):
                y, aux = self._layer(x_in, per_layer, positions, sub, train)
                return self._activation_constraint(y), aux

            # PLD (reference runtime/progressive_layer_drop.py:40; Zhang &
            # He 2020 stochastic depth): layer i bypassed with prob
            # (i+1)/L * (1 - theta) — deeper layers dropped more; no
            # rescale, identity passthrough, all layers active at eval.
            # lax.cond skips the layer's compute at runtime.
            sub, keep_rng = jax.random.split(sub)
            keep_p = 1.0 - (layer_idx.astype(jnp.float32) + 1.0) / L * (
                1.0 - jnp.float32(pld_theta)
            )
            keep = jax.random.bernoulli(keep_rng, keep_p)
            x_new, aux = jax.lax.cond(
                keep, run, lambda x_in: (x_in, jnp.zeros((), jnp.float32)), x
            )
            return (x_new, rng), aux

        def ltd_body(carry, scanned):
            # random-LTD (reference data_routing/basic_layer.py
            # RandomLayerTokenDrop; kernels csrc/random_ltd/): this layer
            # processes ONLY its own random token subset — untouched tokens
            # ride the residual stream past it. The subset is sorted, so
            # causal attention and RoPE see true positions in order.
            from deepspeed_tpu.runtime.data_pipeline.data_routing import (
                gather_tokens,
                scatter_tokens,
            )

            x, rng = carry
            per_layer, idx = scanned  # idx [B, kept]
            if overlap_plan is not None:
                per_layer = overlap_plan.reduce_grads(per_layer)
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            x_sub = gather_tokens(x, idx)
            pos_sub = jnp.take_along_axis(positions, idx, axis=1)
            y, aux = self._layer(x_sub, per_layer, pos_sub, sub, train)
            x_new = self._activation_constraint(scatter_tokens(x, y, idx))
            return (x_new, rng), aux

        if cfg.remat:
            policy = getattr(jax.checkpoint_policies, cfg.remat_policy, None)
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
            ltd_body = jax.checkpoint(ltd_body, policy=policy, prevent_cse=False)

        aux_total = jnp.zeros((), jnp.float32)
        exit_p = None
        with jax.named_scope("layers"):
            if cfg.num_loops > 1:
                if overlap_plan is not None and overlap_plan.prefetch_enabled:
                    refuse_looped(
                        cfg, "the ZeRO-3 layer pipeline (_pipelined_layer_scan)",
                        "its prologue and lookahead gather layers 0..depth-1 once and clamp at the last layer, "
                        "so a second pass would start on the first pass's tail buffers",
                    )
                x, aux_total, exit_p = self._looped_layers(params, x, base_rng, body, exit_distribution)
            elif ltd_active:
                # layer 0 full → LTD layers 1..1+n_ltd on subsets → rest full
                def run_full(x, rng, aux_total, lo, hi):
                    if hi <= lo:
                        return x, rng, aux_total
                    if cfg.scan_layers:
                        sub = jax.tree_util.tree_map(lambda a: a[lo:hi], params["layers"])
                        (x, rng), aux = jax.lax.scan(body, (x, rng), sub)
                        return x, rng, aux_total + jnp.sum(aux)
                    for i in range(lo, hi):
                        (x, rng), aux = body((x, rng), self._layer_params(params, i))
                        aux_total = aux_total + aux
                    return x, rng, aux_total

                x, base_rng, aux_total = run_full(x, base_rng, aux_total, 0, 1)
                if cfg.scan_layers:
                    mid = jax.tree_util.tree_map(
                        lambda a: a[1 : 1 + n_ltd], params["layers"]
                    )
                    (x, base_rng), aux = jax.lax.scan(ltd_body, (x, base_rng), (mid, ltd_idx))
                    aux_total = aux_total + jnp.sum(aux)
                else:
                    for j in range(n_ltd):
                        (x, base_rng), aux = ltd_body(
                            (x, base_rng), (self._layer_params(params, 1 + j), ltd_idx[j])
                        )
                        aux_total = aux_total + aux
                x, base_rng, aux_total = run_full(x, base_rng, aux_total, 1 + n_ltd, L)
            elif cfg.scan_layers and (
                overlap_plan is not None
                and overlap_plan.prefetch_enabled
                and not pld_active
            ):
                x, aux_total = self._pipelined_layer_scan(
                    overlap_plan, params["layers"], x, base_rng, positions, train
                )
            elif cfg.scan_layers:
                xs = (
                    (params["layers"], jnp.arange(L, dtype=jnp.int32))
                    if pld_active
                    else params["layers"]
                )
                (x, _), aux_per_layer = jax.lax.scan(body, (x, base_rng), xs)
                aux_total = jnp.sum(aux_per_layer)
            else:
                for i in range(L):
                    per = self._layer_params(params, i)
                    scanned = (per, jnp.int32(i)) if pld_active else per
                    (x, base_rng), aux = body((x, base_rng), scanned)
                    aux_total = aux_total + aux

        with jax.named_scope("head_loss"):
            if cfg.prenorm:
                x = _norm(x, params["final_norm_scale"], params.get("final_norm_bias"), cfg.norm, cfg.norm_eps)
            if cfg.tie_embeddings:
                logits = x @ params["embed"]["tokens"].astype(self.dtype).T
            else:
                logits = x @ params["lm_head"].astype(self.dtype)
                if cfg.lm_head_bias:
                    logits = logits + params["lm_head_bias"].astype(logits.dtype)
        if exit_distribution:
            return logits, aux_total, exit_p
        return logits, aux_total

    def _looped_layers(self, params, x, rng, body, exit_distribution):
        """``num_loops`` passes of the SAME layers (scope ``loop_pass``), the
        final norm between passes (``pass_norm``; the last pass's is the
        head's own). With ``exit_distribution`` the gate reads every normed
        pass output but the last: ``p_t = lambda_t * prod_{s<t}(1 -
        lambda_s)``, the last pass the remainder, ``[B, T, num_loops]``
        float32. Nothing of the gate is traced otherwise."""
        cfg = self.config
        aux_total = jnp.zeros((), jnp.float32)
        stay = jnp.ones(x.shape[:2], jnp.float32)  # the share of a token still in the loop
        shares = []
        for t in range(cfg.num_loops):
            with jax.named_scope("loop_pass"):
                if cfg.scan_layers:
                    (x, rng), aux = jax.lax.scan(body, (x, rng), params["layers"])
                    aux_total = aux_total + jnp.sum(aux)
                else:
                    for i in range(cfg.num_layers):
                        (x, rng), aux = body((x, rng), self._layer_params(params, i))
                        aux_total = aux_total + aux
            if t == cfg.num_loops - 1:
                break
            with jax.named_scope("pass_norm"):
                x = _norm(x, params["final_norm_scale"], params.get("final_norm_bias"), cfg.norm, cfg.norm_eps)
            if exit_distribution:
                gate = params["exit_gate"]
                lam = jax.nn.sigmoid(x.astype(jnp.float32) @ gate["w"].astype(jnp.float32) + gate["b"].astype(jnp.float32))
                shares.append(lam * stay)
                stay = stay * (1.0 - lam)
        return x, aux_total, jnp.stack(shares + [stay], axis=-1) if exit_distribution else None

    def _scan_layer_step(self, x, per_layer, positions, rng, train):
        """One non-PLD scanned layer iteration: rng split, layer, activation
        constraint. Shared by the plain scan body and the pipelined scan so
        both trace the identical compute (and hence the pipeline stays
        bit-identical to the unpipelined step at every depth)."""
        if rng is not None:
            rng, sub = jax.random.split(rng)
        else:
            sub = None
        y, aux = self._layer(x, per_layer, positions, sub, train)
        return self._activation_constraint(y), rng, aux

    def _pipelined_layer_scan(self, plan, layers, x, base_rng, positions, train):
        """Software-pipelined layer scan: layer *i+depth*'s ZeRO-3 all-gather
        is issued while layer *i* computes, through a ``depth``-deep carry of
        already-gathered per-layer params (prologue gathers layers
        0..depth-1). Depth 0 is the explicit use-point gather — the same
        gather/constraint ops issued at the layer's own iteration, no
        lookahead carry — which is the "unpipelined step" the parity suite
        compares against. Depth only moves where the gather is issued: the
        gather is exact and the rng split order matches the plain scan body,
        so every depth produces bit-identical outputs — only the schedule
        changes. Tail iterations re-gather the last layer into
        never-consumed buffers (index clamp).

        The stack is scanned (``xs``) for the layer's USE: iteration *i*
        gets its own ZeRO-cut slice, the layer's cotangent is transposed
        onto it, and the backward scan hands it out as ``ys`` — one layer's
        gradient written in place an iteration. The prologue and the
        lookahead index ``frozen``, a ``stop_gradient`` view the body
        closes over: nothing flows back into it, so the backward carries no
        accumulator of the stack's whole shape (which it would pass over
        once a layer to add one layer's gradient)."""
        cfg = self.config
        L = cfg.num_layers
        depth = max(0, min(int(plan.depth), L))

        frozen = jax.lax.stop_gradient(layers)

        def pbody(carry, scanned):
            x, rng, bufs = carry
            mine, i = scanned
            if depth:
                cur = plan.use_buffered(mine, bufs[0])
                bufs = bufs[1:] + (
                    plan.gather_layer(frozen, jnp.minimum(i + depth, L - 1)),
                )
            else:
                cur = mine  # the pin below IS the use-point gather
            cur = plan.at_use(cur)
            y, rng, aux = self._scan_layer_step(x, cur, positions, rng, train)
            return (y, rng, bufs), aux

        if cfg.remat:
            policy = getattr(jax.checkpoint_policies, cfg.remat_policy, None)
            pbody = jax.checkpoint(pbody, policy=policy, prevent_cse=False)

        bufs = tuple(plan.gather_layer(frozen, min(j, L - 1)) for j in range(depth))
        (x, _, _), aux_per_layer = jax.lax.scan(
            pbody, (x, base_rng, bufs), (layers, jnp.arange(L, dtype=jnp.int32))
        )
        return x, jnp.sum(aux_per_layer)

    # --- layer streaming (ZeRO-Infinity param offload) -------------------
    def stream_fns(self):
        """Split the forward into (embed, layer, head) programs for the
        layer-streamed param-offload engine (``runtime/zero/param_offload.py``;
        reference analog: ``deepspeed/runtime/swap_tensor/partitioned_param_swapper.py:36``
        + the fetch/release hooks of ``zero/parameter_offload.py:342``).

        Contract: ``embed_fwd(resident, tokens) -> h``,
        ``layer_fwd(layer_params, h, positions, rng, train=True) -> h``,
        ``head_loss(resident, h, labels) -> scalar`` (``labels=None`` →
        logits, the inference head) — where ``resident`` is the param tree
        minus the stacked ``"layers"`` entry and ``layer_params`` is one
        unstacked per-layer tree. MoE aux losses are not routed through this
        path (``MoETransformerLM.stream_fns`` raises)."""
        cfg = self.config
        refuse_looped(
            cfg, "layer streaming (stream_fns: ZeRO-Infinity param offload, the flops profiler's walk)",
            "embed -> each layer once -> head is its whole contract; it has no pass to repeat nor a norm between passes",
        )

        def embed_fwd(resident, tokens):
            tokens = jnp.asarray(tokens)
            x = resident["embed"]["tokens"].astype(self.dtype)[tokens]
            if cfg.position == "learned":
                T = tokens.shape[1]
                x = x + resident["embed"]["pos"].astype(self.dtype)[
                    jnp.arange(T, dtype=jnp.int32)
                ][None]
            if cfg.embed_norm:
                x = _norm(
                    x,
                    resident["embed"]["norm_scale"],
                    resident["embed"].get("norm_bias"),
                    cfg.norm,
                    cfg.norm_eps,
                )
            return x

        def layer_fwd(layer_params, h, positions, rng, train=True):
            out, _aux = self._layer(h, layer_params, positions, rng, train=train)
            return out

        def head_loss(resident, h, labels):
            x = h
            if cfg.prenorm:
                x = _norm(
                    x,
                    resident["final_norm_scale"],
                    resident.get("final_norm_bias"),
                    cfg.norm,
                    cfg.norm_eps,
                )
            if cfg.tie_embeddings:
                logits = x @ resident["embed"]["tokens"].astype(self.dtype).T
            else:
                logits = x @ resident["lm_head"].astype(self.dtype)
                if cfg.lm_head_bias:
                    logits = logits + resident["lm_head_bias"].astype(logits.dtype)
            if labels is None:
                return logits
            return cross_entropy_loss(logits, labels)

        return embed_fwd, layer_fwd, head_loss

    def apply(self, params, batch, *, rngs=None, train: bool = True, pld_theta=None, ltd_idx=None,
              exit_distribution: bool = False):
        """``exit_distribution`` (a looped model with an exit gate, tokens
        alone): returns ``(logits, p)``, ``p`` ``[B, T, num_loops]`` the
        share of each token that would leave after each pass."""
        tokens, labels = _split_batch(batch)
        if exit_distribution:
            logits, _, exit_p = self._forward(params, tokens, rngs, train, exit_distribution=True)
            return logits, exit_p
        logits, aux = self._forward(
            params, tokens, rngs, train, pld_theta=pld_theta, ltd_idx=ltd_idx
        )
        if labels is None:
            return logits
        with jax.named_scope("head_loss"):
            loss = cross_entropy_loss(logits, labels)
        if train:
            # aux is the (already coefficient-scaled) MoE load-balance loss;
            # zero for dense families. Train-only, so eval loss stays pure CE
            # (the reference adds l_aux only in training client code).
            loss = loss + aux
        return loss


def _flash_on_mesh(q, k, v, scale):
    """The fused causal kernel over [B, T, N, D] on the live topology.

    GSPMD cannot partition a Mosaic kernel (the TPU compiler refuses the
    whole step: "Mosaic kernels cannot be automatically partitioned"), so on
    more than one device the call is wrapped in ``shard_map`` and every chip
    runs the kernel on its own batch rows and heads — attention is
    independent per (row, head), so nothing is exchanged. A dim the mesh
    axes do not divide stays whole on every chip, as GSPMD would have
    replicated it. Inside a caller's own ``shard_map`` the operands are
    already per-chip and the kernel is entered directly."""
    from deepspeed_tpu.ops.transformer.flash_attention import flash_attention
    from deepspeed_tpu.parallel.mesh import get_topology

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale)

    topo = get_topology()
    if topo.mesh.devices.size == 1 or jax.sharding.get_abstract_mesh().manual_axes:
        return kernel(q, k, v)
    batch_axes = topo.dense_batch_axes()
    names = (batch_axes,) if isinstance(batch_axes, str) else tuple(batch_axes or ())
    if q.shape[0] % int(np.prod([topo.axis_size(a) for a in names])):
        batch_axes = None
    tp = topo.axis_size("model")
    head_axes = "model" if tp > 1 and q.shape[2] % tp == 0 else None
    spec = P(batch_axes, None, head_axes, None)
    return jax.shard_map(
        kernel, mesh=topo.mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )(q, k, v)


def flash_operand_layout(cfg, topo) -> Optional[Dict[str, Any]]:
    """What training's attention asks of the flash kernels, from the model's
    config and the mesh alone: the heads a chip holds (``_flash_on_mesh``
    splits them over ``model`` where that divides them), their width, and the
    kernels' answer (``ops/transformer/flash_attention.py::operand_layout``).
    None where training does not enter them. The engine records it once at
    build time (``flash.operand_layout``), so a fall back to the transposing
    entry shows in a run's events and not only in a slower step."""
    if not (getattr(cfg, "flash_attention", False) and cfg.position != "alibi" and cfg.causal and cfg.attn_dropout == 0):
        return None
    from deepspeed_tpu.ops.transformer.flash_attention import operand_layout

    sp = topo.axis_size("sequence") if cfg.sequence_parallel else 1
    if sp > 1 and cfg.sequence_parallel_mode == "ring":
        return None  # the ring has an attention of its own
    heads = cfg.num_heads // sp  # Ulysses scatters the heads over the sequence axis
    tp = topo.axis_size("model")
    if heads % tp == 0:
        heads //= tp
    return {"heads_on_a_chip": heads, "head_dim": cfg.head_dim, **operand_layout(heads, cfg.head_dim)._asdict()}


def _expand_gqa(q, k, v):
    """Repeat kv heads up to q's head count — ONLY for consumers whose
    contract requires equal head counts (the fused flash kernel, the
    Ulysses head scatter when sp does not divide NKV). Regular attention
    math must use the grouped einsum path instead (DS-R001)."""
    NH, NKV = q.shape[2], k.shape[2]
    if NKV != NH:
        k = jnp.repeat(k, NH // NKV, axis=2)  # lint: allow(DS-R001)
        v = jnp.repeat(v, NH // NKV, axis=2)  # lint: allow(DS-R001)
    return k, v


def _split_batch(batch):
    if isinstance(batch, dict):
        return batch["input_ids"], batch.get("labels")
    if isinstance(batch, (tuple, list)) and len(batch) == 2:
        return batch[0], batch[1]
    return batch, None
