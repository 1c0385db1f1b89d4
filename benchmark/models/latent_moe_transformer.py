"""Model adapter ``latent_moe_transformer``: the program's
``HybridMoETransformerLM`` (``deepspeed_tpu/models/hybrid_moe.py``) for a model
whose layers are latent-attention ones (one low-rank entry a token in place of
keys and values a head), with a leading dense layer, a shared expert and a
routed FFN that holds a share of its router's experts, from a configuration
file's ``model.kwargs``, which are ``HybridMoEConfig``'s own. GLM-4.7-Flash
runs through it.

``build`` returns the model and its ``shape`` under the keys every family
gives (``dense_transformer.py``; ``head_dim`` is the query/key head's,
``num_kv_heads`` the nominal count: a latent layer stores no head) and the
expert layer's (``moe_transformer.py``; ``num_experts`` is the number HELD,
which is what the program's ``moe_`` counters count, ``num_moe_layers`` the
layers that route: the leading dense ones do not), plus what the latent
readers need: ``num_latent_layers``, ``kv_lora_rank`` (an entry's value part)
and ``qk_rope_head_dim`` (the rotated part beside it).

Seeded weights are the model's own ``init`` but for one leaf: every latent
layer's ``wq_b``, which ``init`` draws like every matrix (standard deviation
0.02, where a score has a standard deviation of ~0.33 and a softmax over
2,000 keys is nearly flat) and which is drawn here at ``model.seeded.wq_b_std``
instead (the configuration file's ``model.seeded`` says why that scale).
"""

from typing import Dict, Tuple


def build(model: Dict) -> Tuple[object, Dict]:
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    cfg = HybridMoEConfig(**model["kwargs"])
    q_scale = float(model["seeded"]["wq_b_std"]) / 0.02  # init draws every matrix at 0.02

    class Seeded(HybridMoETransformerLM):
        def init(self, rng, batch):
            params = super().init(rng, batch)
            for mixer in [params["periods"]["latent"]] + [layer["mixer"] for layer in params.get("leading", ())]:
                mixer["wq_b"] = mixer["wq_b"] * q_scale
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
        "num_latent_layers": cfg.layers_of("latent"),
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
    }
    return Seeded(cfg), shape
