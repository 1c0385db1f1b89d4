"""Model adapter ``conv_moe_transformer``: the program's
``HybridMoETransformerLM`` (``deepspeed_tpu/models/hybrid_moe.py``) for a model
whose mixers are gated short convolutions (a convolution tail a row and NO
recurrent state) beside rotary GQA layers with an RMSNorm a head on q and k,
behind leading layers with a dense FFN, every other layer with a routed FFN of
SwiGLU experts that holds a share of its router's experts, and whose layer list
ends in a partial period (``params["trailing"]``); from a configuration file's
``model.kwargs``, which are ``HybridMoEConfig``'s own. LFM2-24B-A2B runs
through it. (The accepted hybrid adapters give no conv key, which the conv
readers take for a model without such layers.)

``build`` returns the model and its ``shape`` under the keys every family
gives (``dense_transformer.py``; ``head_dim`` the attention layers'), the
expert layer's (``moe_transformer.py``; ``num_experts`` is the number HELD,
which is what the program's ``moe_`` counters count, ``num_moe_layers`` every
routed layer, the trailing ones too, ``expert_matrices`` 3), what the readers
of the conv layers need (``num_conv_layers``, ``conv_channels`` = hidden size,
``conv_taps``, ``conv_tail_bytes_per_row``: the ``taps - 1`` gated products a
row a layer in the served type), ``num_attention_layers`` (the only layers
with KV pages) and ``num_linear_layers`` / ``num_ssm_layers`` 0 (the delta-rule
and state-space readers return None).

Seeded weights are the model's own ``init`` but for three rescalings, each at a
number of ``model.seeded`` (the configuration file says why each), applied to a
leaf by its NAME wherever it lies (the period's stacks, the leading and the
trailing layers' own leaves): every output projection (a mixer's ``wo``, an
FFN's or an expert's ``w_out``) at ``out_std`` (init scales them by ``1 /
sqrt(2 L)``), the attention layers' ``wq`` and ``wk`` at ``qk_std`` (init
draws every matrix at 0.02), and the head norms' scales ``q_norm_scale`` and
``k_norm_scale`` at ``qk_norm_scale`` (init: ones).
"""

from typing import Dict, Tuple


def build(model: Dict) -> Tuple[object, Dict]:
    import jax

    from deepspeed_tpu.models.hybrid_moe import HybridMoEConfig, HybridMoETransformerLM

    cfg = HybridMoEConfig(**model["kwargs"])
    seeded = model["seeded"]
    init_std, init_out_std = 0.02, 0.02 / (2 * cfg.num_layers) ** 0.5  # what init draws a matrix and an output projection at
    factor = {
        "wo": float(seeded["out_std"]) / init_out_std, "w_out": float(seeded["out_std"]) / init_out_std,
        "wq": float(seeded["qk_std"]) / init_std, "wk": float(seeded["qk_std"]) / init_std,
        "q_norm_scale": float(seeded["qk_norm_scale"]), "k_norm_scale": float(seeded["qk_norm_scale"]),
    }

    class Seeded(HybridMoETransformerLM):
        def init(self, rng, batch):
            def rescaled(path, leaf):
                return leaf * factor.get(getattr(path[-1], "key", None), 1.0)

            return jax.tree_util.tree_map_with_path(rescaled, super().init(rng, batch))

    itemsize = 4 if cfg.dtype == "float32" else 2
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
        "num_attention_layers": cfg.layers_of("softmax"),
        "num_linear_layers": cfg.layers_of("linear"),
        "num_ssm_layers": cfg.layers_of("ssm"),
        "num_conv_layers": cfg.layers_of("conv"),
        "conv_channels": cfg.hidden_size,
        "conv_taps": cfg.conv_kernel,
        "conv_tail_bytes_per_row": (cfg.conv_kernel - 1) * cfg.hidden_size * itemsize,
    }
    return Seeded(cfg), shape
