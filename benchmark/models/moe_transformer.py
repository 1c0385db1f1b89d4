"""Model adapter ``moe_transformer``: the program's ``MoETransformerLM``
(``deepspeed_tpu/models/moe_transformer.py``) from a configuration file's
``model.kwargs``, which are ``MoETransformerConfig``'s own. OLMoE runs
through it.

``build`` returns the model and its ``shape`` under the keys every family
gives (``dense_transformer.py``), plus the expert layer's own: what the
experts' readers need to count weights and operations.

Seeded weights stand in for a trained checkpoint, and a trained router is
peaked where the training initialisation (``wg`` of standard deviation 0.02)
is nearly flat: at H 2048 its logits have a standard deviation of 0.9, the 8
chosen gates of 64 sum to 0.39, and the expert branch moves the logits so
little that no check of the served tokens can see the experts' arithmetic.
``model.seeded.router_std`` is the standard deviation the router's weights
are given instead (the model's ``init`` scaled, so the same seed gives the
same directions); without the key the initialisation stands.
"""

from typing import Dict, Tuple

TRAINING_ROUTER_STD = 0.02  # moe/layer.py::MoE.init


def build(model: Dict) -> Tuple[object, Dict]:
    from deepspeed_tpu.models import MoETransformerLM
    from deepspeed_tpu.models.moe_transformer import MoETransformerConfig

    cfg = MoETransformerConfig(**model["kwargs"])
    shape = {
        "vocab_size": cfg.vocab_size,
        "max_seq_len": cfg.max_seq_len,
        "num_layers": cfg.num_layers,
        "hidden_size": cfg.hidden_size,
        "num_heads": cfg.num_heads,
        "num_kv_heads": cfg.num_kv_heads or cfg.num_heads,
        "head_dim": cfg.head_dim,
        "remat": bool(cfg.remat),
        "num_experts": cfg.num_experts,
        "experts_per_token": cfg.moe_top_k,
        "expert_intermediate_size": cfg.expert_intermediate_size,
        "expert_matrices": 3 if cfg.activation in ("swiglu", "geglu") else 2,
    }
    lm = MoETransformerLM(cfg)
    router_std = model.get("seeded", {}).get("router_std")
    if router_std is not None:
        training_init = lm.init

        def init(rng, batch):
            params = training_init(rng, batch)
            gate = params["layers"]["moe"]["gate"]
            gate["wg"] = gate["wg"] * (router_std / TRAINING_ROUTER_STD)
            return params

        lm.init = init
    return lm, shape
