"""Model adapter ``linear_latent_moe_transformer``: the program's
``HybridMoETransformerLM`` (``deepspeed_tpu/models/hybrid_moe.py``) for a model
whose layers are delta-rule linear-attention ones (a constant recurrent state a
row) beside latent-attention ones (one low-rank entry a token), with leading
dense layers of either kind, a shared expert and a routed FFN that holds a
share of its router's experts, from a configuration file's ``model.kwargs``,
which are ``HybridMoEConfig``'s own. Kimi-Linear runs through it.
(``hybrid_moe_transformer.py`` gives no latent keys and
``latent_moe_transformer.py`` no linear ones, and rescales a leaf, ``wq_b``,
that a query without a low rank does not have.)

``build`` returns the model and its ``shape`` under the keys every family
gives (``dense_transformer.py``; ``head_dim`` is the latent layers' query/key
head's, ``num_kv_heads`` the nominal count: neither mixer stores a KV head),
the expert layer's (``moe_transformer.py``; ``num_experts`` is the number HELD,
which is what the program's ``moe_`` counters count, ``num_moe_layers`` the
layers that route: the leading dense ones do not), what the readers of the
state layers need (``num_linear_layers``, leading ones counted, the state's
shape a row a layer ``linear_heads`` x ``linear_head_dim`` x
``linear_head_dim`` in float32, the convolution's taps) and what the latent
readers need (``num_latent_layers``, ``kv_lora_rank``, an entry's value part,
and ``qk_rope_head_dim``, the shared part beside it, rotated or not).

Seeded weights are the model's own ``init`` but for one leaf: every latent
layer's ``wq``, which ``init`` draws like every matrix (standard deviation
0.02) and which is drawn here at ``model.seeded.wq_std`` instead (the
configuration file's ``model.seeded`` says why that scale). A linear layer's
``wq`` keeps init's: its query is l2-normalised.
"""

from typing import Dict, Tuple


def build(model: Dict) -> Tuple[object, Dict]:
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    cfg = HybridMoEConfig(**model["kwargs"])
    q_scale = float(model["seeded"]["wq_std"]) / 0.02  # init draws every matrix at 0.02

    class Seeded(HybridMoETransformerLM):
        def init(self, rng, batch):
            params = super().init(rng, batch)
            leading = [layer["mixer"] for kind, layer in zip(cfg.layer_types, params.get("leading", ())) if kind == "latent"]
            for mixer in [params["periods"]["latent"]] + leading:
                mixer["wq"] = mixer["wq"] * q_scale
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
        "num_linear_layers": cfg.layers_of("linear"),
        "linear_heads": cfg.linear_num_heads,
        "linear_head_dim": cfg.linear_head_dim,
        "linear_conv_kernel": cfg.linear_conv_kernel,
        "num_latent_layers": cfg.layers_of("latent"),
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
    }
    return Seeded(cfg), shape
