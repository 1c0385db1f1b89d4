"""Model adapter ``dense_transformer``: the program's ``TransformerLM``
(``deepspeed_tpu/models/transformer.py``) from a configuration file's
``model.kwargs``, which are ``TransformerConfig``'s own. GPT-2 and Mistral
both run through it. Another family of the program (``MoETransformerLM``,
...) gets an adapter file of its own beside this one, and a configuration
names it as ``model.adapter``.

``build`` returns the model and its ``shape``: the numbers the drivers (the
vocabulary, the positions) and the per-layer readers (layers, heads, head
size, remat) take from a model, under these keys whatever the family.
"""

from typing import Dict, Tuple


def build(model: Dict) -> Tuple[object, Dict]:
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig

    cfg = TransformerConfig(**model["kwargs"])
    shape = {
        "vocab_size": cfg.vocab_size,
        "max_seq_len": cfg.max_seq_len,
        "num_layers": cfg.num_layers,
        "hidden_size": cfg.hidden_size,
        "num_heads": cfg.num_heads,
        "num_kv_heads": cfg.num_kv_heads or cfg.num_heads,
        "head_dim": cfg.head_dim,
        "remat": bool(cfg.remat),
    }
    return TransformerLM(cfg), shape
