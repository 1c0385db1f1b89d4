"""Model adapter ``ssm_moe_transformer``: the program's
``HybridMoETransformerLM`` (``deepspeed_tpu/models/hybrid_moe.py``) for a model
whose blocks are ONE sublayer each (``layer_types`` names ``ffn`` blocks): a
Mamba-2 state-space mixer (a constant recurrent state a row, ``B`` and ``C`` in
groups) alone, a softmax-attention mixer alone, or a routed FFN alone that
holds a share of its router's experts beside a shared expert, the experts of
TWO matrices with a pointwise activation between them; from a configuration
file's ``model.kwargs``, which are ``HybridMoEConfig``'s own.
NVIDIA-Nemotron-3-Nano-30B-A3B runs through it. (``ssm_dense_transformer.py``
gives no expert key, and the adapters that give expert keys give no
state-space ones and say ``expert_matrices`` 3.)

``build`` returns the model and its ``shape`` under the keys every family
gives (``dense_transformer.py``; ``head_dim`` the attention blocks';
``num_layers`` the blocks), the expert layer's (``moe_transformer.py``;
``num_experts`` is the number HELD, which is what the program's ``moe_``
counters count, ``num_moe_layers`` the FFN blocks, ``expert_matrices`` 2: an
expert is ``w_in`` and ``w_out``), what the readers of the state-space layers
need (``num_ssm_layers``, the state's shape a row a layer ``ssm_heads`` x
``ssm_head_dim`` x ``ssm_state`` in float32, the convolved channels
``ssm_conv_channels`` = ``d_inner + 2 groups state`` and the taps
``ssm_conv_kernel``), ``num_attention_layers`` (the only blocks with KV pages)
and ``num_linear_layers`` 0 (the delta-rule readers return None).

Seeded weights are the model's own ``init`` but for one leaf: every attention
block's ``wq``, which ``init`` draws like every matrix (standard deviation
0.02) and which is drawn here at ``model.seeded.wq_std`` instead (the
configuration file's ``model.seeded`` says why that scale, and why the
router's selection bias keeps init's 0.02: large enough to move one choice in
seven, small enough to leave the experts' load even).
"""

from typing import Dict, Tuple


def build(model: Dict) -> Tuple[object, Dict]:
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    cfg = HybridMoEConfig(**model["kwargs"])
    q_scale = float(model["seeded"]["wq_std"]) / 0.02  # init draws every matrix at 0.02

    class Seeded(HybridMoETransformerLM):
        def init(self, rng, batch):
            params = super().init(rng, batch)
            params["periods"]["softmax"]["wq"] = params["periods"]["softmax"]["wq"] * q_scale
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
        "expert_matrices": 2,
        "num_moe_layers": cfg.num_moe_layers,
        "num_attention_layers": cfg.layers_of("softmax"),
        "num_linear_layers": cfg.layers_of("linear"),
        "num_ssm_layers": cfg.layers_of("ssm"),
        "ssm_heads": cfg.ssm_num_heads,
        "ssm_head_dim": cfg.ssm_head_dim,
        "ssm_state": cfg.ssm_state,
        "ssm_conv_channels": cfg.ssm_conv_channels,
        "ssm_conv_kernel": cfg.ssm_conv_kernel,
    }
    return Seeded(cfg), shape
