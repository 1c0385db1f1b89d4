"""Model configs for the built-in transformer families.

One decoder implementation (``models/transformer.py``) parameterized to cover
the reference's injected model zoo (``deepspeed/module_inject/containers/``:
gpt2, llama, gptj, gptneox, opt, bloom, megatron): norm type, positional
scheme, activation, attention variant (MHA/GQA) are all config switches.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: Optional[int] = None  # default: 4h (gelu) or 8h/3 rounded (swiglu)
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # GQA; None = MHA
    head_dim: Optional[int] = None
    max_seq_len: int = 2048

    causal: bool = True  # False = bidirectional (encoder) attention
    attn_softmax_scale: Optional[float] = None  # None = 1/sqrt(head_dim); GPT-Neo uses 1.0
    prenorm: bool = True  # False = post-LN (BERT family): norm AFTER residual, no final norm
    parallel_residual: bool = False  # GPT-J/NeoX: x + attn(norm(x)) + mlp(norm'(x))
    shared_parallel_norm: bool = False  # GPT-J: both parallel branches read ONE norm (ln_1)
    rope_dim: Optional[int] = None  # partial rotary (GPT-J rotary_dim / NeoX rotary_pct); None = full head_dim
    lm_head_bias: bool = False  # GPT-J: untied head carries a bias
    embed_norm: bool = False  # LayerNorm on the embedding output (BERT family)
    norm: str = "layernorm"  # layernorm | rmsnorm
    norm_eps: float = 1e-5
    position: str = "learned"  # learned | rope | alibi | none
    rope_theta: float = 10000.0
    activation: str = "gelu"  # gelu | swiglu | relu | geglu | quick_gelu
    tie_embeddings: bool = True
    attn_dropout: float = 0.0
    hidden_dropout: float = 0.0
    use_bias: bool = True  # linear biases (gpt2 yes, llama no)
    qkv_bias: Optional[bool] = None  # override for qkv projs
    # QK-norm. "projection": RMSNorm (``norm_eps``, learned scale) over the WHOLE
    # q projection [NH*D] and the whole k projection [NKV*D], before the split
    # into heads and RoPE (the OLMoE family), so the cached k is the normed one.
    # "head" (the multi-kind family alone, ``models/hybrid_moe.py``): RMSNorm over
    # each head's ``head_dim`` features of q and of k, one learned scale [D] each
    qk_norm: Optional[str] = None
    # a looped (universal-transformer) stack: the SAME ``num_layers`` layers of
    # weights run ``num_loops`` times a token, the final norm after every pass
    # (its output is the next pass's input), each pass attending to the keys
    # and values that this pass itself made: ``cache_layers`` below counts
    # ``num_loops * num_layers`` layers of cache over ``num_layers`` of weights
    num_loops: int = 1
    # sandwich norms: a second norm a sublayer, on the sublayer's OUTPUT before
    # the residual add (leaves ``attn_post_norm_scale`` / ``mlp_post_norm_scale``)
    post_sublayer_norm: bool = False
    # a looped model's exit gate: ``sigmoid(h w + b)`` of every pass's normed
    # output gives the distribution over passes a token could leave after
    # (``TransformerLM.apply(..., exit_distribution=True)``). A token leaves
    # at the first pass whose cumulated share reaches ``early_exit_threshold``:
    # at 1.0, the only value served, that is the last pass for every token and
    # the gate moves no logit
    exit_gate: bool = False
    early_exit_threshold: float = 1.0
    dtype: str = "bfloat16"  # computation dtype for activations

    # sparse embedding gradients (reference engine.py:2398: DP-reduce the
    # compact (ids, rows) pairs instead of the dense table; requires an
    # untied table — a tied LM head makes the table grad dense anyway)
    sparse_embedding_grads: bool = False

    # engineering knobs
    remat: bool = True  # jax.checkpoint each layer
    remat_policy: str = "nothing_saveable"
    scan_layers: bool = True  # lax.scan over stacked layer params
    flash_attention: bool = True  # Pallas fused-attention kernel for causal attention (einsum for alibi / attention dropout)
    sequence_parallel: bool = False  # sequence parallelism over the 'sequence' axis
    sequence_parallel_mode: str = "ulysses"  # ulysses (all-to-all) | ring (ppermute)

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.intermediate_size is None:
            if self.activation in ("swiglu", "geglu"):
                # llama convention: 2/3 * 4h rounded to a multiple of 256
                self.intermediate_size = 256 * round(self.hidden_size * 8 / 3 / 256)
            else:
                self.intermediate_size = 4 * self.hidden_size
        if self.qkv_bias is None:
            self.qkv_bias = self.use_bias
        if self.qk_norm not in (None, "projection", "head"):
            raise ValueError(f"unknown qk_norm {self.qk_norm!r}; expected None, 'projection' or 'head'")
        if self.qk_norm == "head" and not hasattr(self, "layer_types"):
            raise NotImplementedError(
                "qk_norm='head' (a norm over each head's features) is the multi-kind family's (models/hybrid_moe.py: "
                "HybridMoEConfig); a uniform model norms the whole projection: qk_norm='projection'"
            )
        if self.sequence_parallel_mode not in ("ulysses", "ring"):
            raise ValueError(
                f"unknown sequence_parallel_mode {self.sequence_parallel_mode!r}; "
                "expected 'ulysses' or 'ring'"
            )
        if self.num_loops < 1:
            raise ValueError(f"num_loops counts the passes over the layer stack: at least 1, got {self.num_loops}")
        if self.early_exit_threshold < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold={self.early_exit_threshold} < 1 lets rows of one step leave the loop after "
                "different passes: the scheduler's step has one q_len a row and no count of passes a row, "
                "and a row that left early has no keys in the later passes' cache layers for its successors "
                "to attend to; only 1.0 (every token runs every pass) is served"
            )
        if (self.num_loops > 1 or self.post_sublayer_norm) and (not self.prenorm or self.parallel_residual):
            raise ValueError(
                "num_loops > 1 and post_sublayer_norm describe a pre-norm sequential layer (the final norm runs "
                "between passes; the second norm sits on a sublayer's output before its residual add): "
                "prenorm=True and parallel_residual=False"
            )
        if self.exit_gate and self.num_loops < 2:
            raise ValueError("exit_gate is a looped model's (num_loops > 1): with one pass there is nothing to leave")
        if self.shared_parallel_norm and not self.parallel_residual:
            raise ValueError("shared_parallel_norm requires parallel_residual=True")
        if self.parallel_residual and not self.prenorm:
            raise ValueError(
                "parallel_residual requires prenorm=True (both branches read "
                "normed x; a post-LN parallel layer is not a real architecture)"
            )
        if self.lm_head_bias and self.tie_embeddings:
            raise ValueError("lm_head_bias requires an untied head (tie_embeddings=False)")
        if self.sparse_embedding_grads and self.tie_embeddings:
            raise ValueError(
                "sparse_embedding_grads requires tie_embeddings=False: a tied "
                "LM head contributes a dense gradient to the same table, so "
                "there is nothing sparse to reduce"
            )


def cache_layers(cfg) -> int:
    """Layers of K and V cache a token keeps: the ONE place that counts them,
    read by the page pool (``kv_pool.init_paged_cache``, and through its
    shapes ``bytes_per_token`` / ``memory_report()``), the dense workspace
    (``decode.init_cache``) and the benchmark's adapters. A uniform model
    keeps a layer of cache for every layer of weights in every pass over the
    stack (``num_loops``: pass ``t``'s layer ``l`` owns cache layer
    ``t * num_layers + l``); a model with layers of more than one kind keeps
    keys and values a head in its softmax layers only."""
    if getattr(cfg, "layer_types", None):
        return cfg.layers_of("softmax")
    return cfg.num_layers * getattr(cfg, "num_loops", 1)


def refuse_looped(cfg, what: str, missing: str) -> None:
    """``what`` runs the layer stack once a token: a looped model
    (``num_loops > 1``) is refused where ``what`` is built, with the missing
    piece named, and never silently run for one pass."""
    if getattr(cfg, "num_loops", 1) > 1:
        raise NotImplementedError(f"{what} does not support a looped model (num_loops={cfg.num_loops}): {missing}")


def has_state_layers(cfg) -> bool:
    """Whether a model keeps, beside the keys and values of its full-attention
    layers, something of a row that exists at the row's newest positions only
    (``models/hybrid_moe.py``: a ``layer_types`` that names ``linear`` or
    ``ssm``, whose layers keep a recurrent state, ``conv``, whose layers keep
    a convolution's tail, or ``window`` / ``window_latent``, whose layers keep
    a ring of the newest pages: keys and values a head, or latents)."""
    return bool({"linear", "ssm", "conv", "window", "window_latent"} & set(getattr(cfg, "layer_types", None) or ()))


def has_latent_layers(cfg) -> bool:
    """Whether some layer keeps one latent entry a token under the page table
    in place of keys and values a head (``layer_types`` naming ``latent``, or
    ``sparse_latent``, which keeps its indexer's key beside it): pages of a
    third (and a fourth) array, which what copies, shares or rolls back K and V
    pages does not know."""
    return bool({"latent", "sparse_latent"} & set(getattr(cfg, "layer_types", None) or ()))


def gpt2_config(size: str = "125m", **overrides) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8, vocab_size=1024, max_seq_len=512),
        "125m": dict(hidden_size=768, num_layers=12, num_heads=12),
        "350m": dict(hidden_size=1024, num_layers=24, num_heads=16),
        "1.3b": dict(hidden_size=2048, num_layers=24, num_heads=16),
        "2.7b": dict(hidden_size=2560, num_layers=32, num_heads=32),
    }
    base = dict(
        vocab_size=50257,
        max_seq_len=1024,
        norm="layernorm",
        position="learned",
        activation="gelu",
        use_bias=True,
        tie_embeddings=True,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def llama_config(size: str = "7b", **overrides) -> TransformerConfig:
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8, vocab_size=32000, max_seq_len=512),
        "1b": dict(hidden_size=2048, num_layers=22, num_heads=32, num_kv_heads=4, vocab_size=32000),
        "7b": dict(hidden_size=4096, num_layers=32, num_heads=32, vocab_size=32000, max_seq_len=4096),
        "13b": dict(hidden_size=5120, num_layers=40, num_heads=40, vocab_size=32000, max_seq_len=4096),
        "70b": dict(
            hidden_size=8192,
            num_layers=80,
            num_heads=64,
            num_kv_heads=8,
            intermediate_size=28672,
            vocab_size=32000,
            max_seq_len=4096,
        ),
    }
    base = dict(
        norm="rmsnorm",
        norm_eps=1e-5,
        position="rope",
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def qwen2_config(size: str = "7b", **overrides) -> TransformerConfig:
    """Qwen2 family: the llama body (RMSNorm + RoPE + SwiGLU, no output
    biases) with BIASED q/k/v projections and GQA."""
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8, num_kv_heads=2,
                     vocab_size=1024, max_seq_len=512),
        "0.5b": dict(hidden_size=896, num_layers=24, num_heads=14, num_kv_heads=2,
                     intermediate_size=4864, vocab_size=151936, tie_embeddings=True),
        "7b": dict(hidden_size=3584, num_layers=28, num_heads=28, num_kv_heads=4,
                   intermediate_size=18944, vocab_size=152064, max_seq_len=4096),
    }
    base = dict(
        norm="rmsnorm",
        norm_eps=1e-6,
        position="rope",
        rope_theta=1e6,  # all Qwen2 sizes use base 1e6 (like mixtral_config)
        activation="swiglu",
        use_bias=False,
        qkv_bias=True,
        tie_embeddings=False,
        max_seq_len=2048,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)


def bert_config(size: str = "large", **overrides) -> TransformerConfig:
    """Encoder config: bidirectional (non-causal) attention."""
    presets = {
        "base": dict(hidden_size=768, num_layers=12, num_heads=12),
        "large": dict(hidden_size=1024, num_layers=24, num_heads=16),
    }
    base = dict(
        vocab_size=30522,
        max_seq_len=512,
        causal=False,
        norm="layernorm",
        position="learned",
        activation="gelu",
        use_bias=True,
        tie_embeddings=False,
    )
    base.update(presets[size])
    base.update(overrides)
    return TransformerConfig(**base)
