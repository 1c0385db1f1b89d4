"""Model adapter ``hybrid_moe_transformer``: the program's
``HybridMoETransformerLM`` (``deepspeed_tpu/models/hybrid_moe.py``: layers of
more than one kind, a routed FFN that holds a share of its router's experts,
a shared expert) from a configuration file's ``model.kwargs``, which are
``HybridMoEConfig``'s own. Solar-Open2 runs through it.

``build`` returns the model and its ``shape`` under the keys every family
gives (``dense_transformer.py``) and the expert layer's (``moe_transformer.py``;
``num_experts`` is the number HELD, which is what the program's ``moe_``
counters count), plus what the readers of the state layers need:
``num_attention_layers`` (softmax layers, the only ones with KV pages),
``num_linear_layers``, the state's shape a row a layer (``linear_heads`` x
``linear_head_dim`` x ``linear_head_dim``, float32), the convolution's taps
and ``router_experts``, the router's width.

Seeded weights are the model's own ``init`` as it stands: no leaf is rescaled
(the configuration file's ``model.seeded`` says why none needs to be).
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
        "num_attention_layers": cfg.layers_of("softmax"),
        "num_linear_layers": cfg.layers_of("linear"),
        "linear_heads": cfg.linear_num_heads,
        "linear_head_dim": cfg.linear_head_dim,
        "linear_conv_kernel": cfg.linear_conv_kernel,
    }
    return HybridMoETransformerLM(cfg), shape
