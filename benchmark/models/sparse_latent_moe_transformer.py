"""Model adapter ``sparse_latent_moe_transformer``: the program's
``HybridMoETransformerLM`` (``deepspeed_tpu/models/hybrid_moe.py``) for a model
whose layers are latent-attention ones of TWO kinds, full layers that attend
the ``index_topk`` keys a learned indexer chooses (``sparse_latent``) and
window layers whose ring holds latents of their own rank (``window_latent``),
with a gate a head on both, a leading dense layer, a shared expert and a
routed FFN that holds a share of its router's experts, from a configuration
file's ``model.kwargs``, which are ``HybridMoEConfig``'s own. dots3-note-prev
runs through it.

``build`` returns the model and its ``shape`` under the keys every family
gives (``dense_transformer.py``; ``num_heads`` and ``head_dim`` are the full
layers', ``num_kv_heads`` the nominal count: a latent layer stores no head) and
the expert layer's (``moe_transformer.py``; ``num_experts`` is the number
HELD, ``num_moe_layers`` the layers that route), plus what the readers of the
two kinds need: ``num_sparse_layers`` with the indexer's ``index_heads``,
``index_head_dim`` and ``index_topk`` and an entry's two parts
(``kv_lora_rank``, ``qk_rope_head_dim``); ``num_window_latent_layers`` with
``window``, ``window_heads`` and ITS entry's two parts. It states no
``num_latent_layers``: the accepted latent readers reckon the latent kernel's
calls, which this model makes none of.

Seeded weights are the model's own ``init``, no leaf rescaled: with the
published rescale of both low ranks its 0.02 gives scores of a standard
deviation near two (the configuration file's ``model.seeded`` has the
arithmetic and the readings).
"""

from typing import Dict, Tuple


def build(model: Dict) -> Tuple[object, Dict]:
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    cfg = HybridMoEConfig(**model["kwargs"])
    full, window = cfg.latent_dims("sparse_latent"), cfg.latent_dims("window_latent")
    shape = {
        "vocab_size": cfg.vocab_size,
        "max_seq_len": cfg.max_seq_len,
        "num_layers": cfg.num_layers,
        "hidden_size": cfg.hidden_size,
        "num_heads": cfg.num_heads,
        "num_kv_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "remat": False,
        "num_experts": cfg.num_experts,
        "router_experts": cfg.moe_router_experts,
        "experts_per_token": cfg.moe_top_k,
        "expert_intermediate_size": cfg.expert_intermediate_size,
        "expert_matrices": 3,
        "num_moe_layers": cfg.num_moe_layers,
        "num_sparse_layers": cfg.layers_of("sparse_latent"),
        "index_heads": cfg.index_num_heads,
        "index_head_dim": cfg.index_head_dim,
        "index_topk": cfg.index_topk,
        "kv_lora_rank": full.kv_rank,
        "qk_rope_head_dim": full.rope,
        "num_window_latent_layers": cfg.layers_of("window_latent"),
        "window": cfg.window,
        "window_heads": window.heads,
        "window_kv_lora_rank": window.kv_rank,
        "window_qk_rope_head_dim": window.rope,
    }
    return HybridMoETransformerLM(cfg), shape
