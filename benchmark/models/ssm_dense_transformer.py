"""Model adapter ``ssm_dense_transformer``: the program's
``HybridMoETransformerLM`` (``deepspeed_tpu/models/hybrid_moe.py``) for a model
whose layers are Mamba-2 state-space mixers (a constant recurrent state a row)
beside softmax-attention ones, with a DENSE FFN in every layer and no expert
anywhere (``num_experts`` 0), from a configuration file's ``model.kwargs``,
which are ``HybridMoEConfig``'s own. granite-4.0-h-micro runs through it. (The
accepted hybrid adapters all give expert keys, which the expert readers take
for a routed model's.)

``build`` returns the model and its ``shape`` under the keys every family
gives (``dense_transformer.py``; ``head_dim`` the attention layers'), what the
readers of the state-space layers need (``num_ssm_layers``, the state's shape
a row a layer ``ssm_heads`` x ``ssm_head_dim`` x ``ssm_state`` in float32, the
convolved channels ``ssm_conv_channels`` and the taps ``ssm_conv_kernel``),
``num_attention_layers`` (the only layers with KV pages) and
``num_linear_layers`` 0 (the delta-rule readers return None). No expert key.

Seeded weights are the model's own ``init`` but for three rescalings, each at a
number of ``model.seeded`` (the configuration file says why each): every
attention layer's ``wq`` at ``wq_std`` (init draws every matrix at 0.02: a
softmax scale of 1/64 would leave attention flat), the three output
projections (a state-space mixer's and an attention layer's ``wo``, the FFN's
``w_out``) at ``out_std`` (init scales them by ``1 / sqrt(2 L)``) and the tied
table at ``embed_std``: with the embedding 12-fold on the residual stream and
the layers' branches at 0.22 of a depth-scaled projection, a token's own row of
the TIED table would win every arg-max by 40 standard deviations, whatever
the layers compute.
"""

from typing import Dict, Tuple


def build(model: Dict) -> Tuple[object, Dict]:
    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    cfg = HybridMoEConfig(**model["kwargs"])
    seeded = model["seeded"]
    init_std, init_out_std = 0.02, 0.02 / (2 * cfg.num_layers) ** 0.5  # what init draws a matrix and an output projection at

    class Seeded(HybridMoETransformerLM):
        def init(self, rng, batch):
            params = super().init(rng, batch)
            periods = params["periods"]
            periods["softmax"]["wq"] = periods["softmax"]["wq"] * (float(seeded["wq_std"]) / init_std)
            for tree, leaf in ((periods["ssm"], "wo"), (periods["softmax"], "wo"), (periods["ffn"], "w_out")):
                tree[leaf] = tree[leaf] * (float(seeded["out_std"]) / init_out_std)
            params["embed"]["tokens"] = params["embed"]["tokens"] * (float(seeded["embed_std"]) / init_std)
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
