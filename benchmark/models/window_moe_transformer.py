"""Model adapter ``window_moe_transformer``: the program's
``HybridMoETransformerLM`` (``deepspeed_tpu/models/hybrid_moe.py``) for a model
whose layers are full-attention and sliding-window ones with head layouts of
their own, a leading dense layer and a routed FFN that holds a share of its
router's experts, from a configuration file's ``model.kwargs``, which are
``HybridMoEConfig``'s own. MiMo-V2.5 runs through it.

``build`` returns the model and its ``shape`` under the keys every family
gives (``dense_transformer.py``; ``num_kv_heads`` and ``head_dim`` are the full
layers' and the key's) and the expert layer's (``moe_transformer.py``;
``num_experts`` is the number HELD, which is what the program's ``moe_``
counters count, ``num_moe_layers`` the layers that route: the leading dense
ones do not), plus what the readers of the two kinds of attention need:
``num_full_layers`` and ``num_window_layers``, ``window``, each kind's KV
heads, ``qk_head_dim`` and ``v_head_dim``.

Seeded weights are the model's own ``init`` but for one leaf: the window
layers' sink biases, which ``init`` draws like every vector (standard
deviation 0.02, where a sink carries no weight against scores of standard
deviation 1.6) and which are drawn here at ``model.seeded.sink_std`` instead
(the configuration file's ``model.seeded`` says why that scale).
"""

from typing import Dict, Tuple


def build(model: Dict) -> Tuple[object, Dict]:
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    cfg = HybridMoEConfig(**model["kwargs"])
    sink_scale = float(model["seeded"]["sink_std"]) / 0.02  # init draws every vector at 0.02

    class Seeded(HybridMoETransformerLM):
        def init(self, rng, batch):
            params = super().init(rng, batch)
            window = params["periods"]["window"]
            window["sinks"] = window["sinks"] * sink_scale
            return params

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
        "full_kv_heads": cfg.num_kv_heads,
        "window_kv_heads": cfg.window_num_kv_heads,
        "qk_head_dim": cfg.head_dim,
        "v_head_dim": cfg.v_head_dim,
    }
    return Seeded(cfg), shape
