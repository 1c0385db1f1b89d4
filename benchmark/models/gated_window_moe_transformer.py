"""Model adapter ``gated_window_moe_transformer``: the program's
``HybridMoETransformerLM`` (``deepspeed_tpu/models/hybrid_moe.py``) for a model
whose full-attention and sliding-window layers have QUERY-head counts and
rotary terms of their own and a sigmoid gate a head on their output, a leading
dense layer and a routed FFN with a shared expert that holds a share of its
router's experts, from a configuration file's ``model.kwargs``, which are
``HybridMoEConfig``'s own. Laguna-S-2.1 runs through it.

``build`` returns the model and its ``shape`` under the keys every family
gives (``dense_transformer.py``; ``num_heads``, ``num_kv_heads`` and
``head_dim`` are the full layers') and the expert layer's
(``window_moe_transformer.py``: ``num_experts`` is the number HELD,
``num_moe_layers`` the layers that route), plus what the readers of the two
kinds of attention need: ``num_full_layers`` and ``num_window_layers``,
``window``, each kind's query heads (``full_heads``, ``window_heads``) and KV
heads, ``qk_head_dim`` and ``v_head_dim``.

Seeded weights are the model's own ``init``, no leaf rescaled: at these widths
its 0.02 gives scores, gate logits and router logits of a standard deviation
near one, so every path carries weight (the configuration file's
``model.seeded`` has the arithmetic and the readings).
"""

from typing import Dict, Tuple


def build(model: Dict) -> Tuple[object, Dict]:
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    cfg = HybridMoEConfig(**model["kwargs"])
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
        "num_full_layers": cfg.layers_of("softmax"),
        "num_window_layers": cfg.layers_of("window"),
        "window": cfg.window,
        "full_heads": cfg.heads_of("softmax"),
        "window_heads": cfg.heads_of("window"),
        "full_kv_heads": cfg.kv_heads_of("softmax"),
        "window_kv_heads": cfg.kv_heads_of("window"),
        "qk_head_dim": cfg.head_dim,
        "v_head_dim": cfg.v_head_dim,
    }
    return HybridMoETransformerLM(cfg), shape
