"""Model adapter ``looped_dense_transformer``: the program's ``TransformerLM``
(``deepspeed_tpu/models/transformer.py``) for a model whose layer stack runs
more than once a token (``num_loops``; Ouro-2.6B runs through it), from a
configuration file's ``model.kwargs``, which are ``TransformerConfig``'s own.

``build`` returns the model and its ``shape`` under the keys every family
gives (``dense_transformer.py``). ``num_layers`` is the layers a STEP runs,
the model's cache layers (``models/config.py::cache_layers``: ``num_loops x``
the layers of weights, 192 for 48 run four times) and the ragged kernel's
calls a step: what every reader that takes ``m["num_layers"]`` multiplies by.
``weight_layers`` and ``num_loops`` stand beside it for the readers of the
loop (``loop_pass_device_ms``, ``weight_stream_*``), with
``intermediate_size``, ``swiglu`` and ``tie_embeddings`` for the count of a
pass's weights (``benchmark/kernels/dense_weight_stream.py``).
"""

from typing import Dict, Tuple


def build(model: Dict) -> Tuple[object, Dict]:
    from deepspeed_tpu.models import TransformerLM
    from deepspeed_tpu.models.config import TransformerConfig, cache_layers

    cfg = TransformerConfig(**model["kwargs"])
    shape = {
        "vocab_size": cfg.vocab_size,
        "max_seq_len": cfg.max_seq_len,
        "num_layers": cache_layers(cfg),
        "weight_layers": cfg.num_layers,
        "num_loops": cfg.num_loops,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "swiglu": cfg.activation in ("swiglu", "geglu"),
        "tie_embeddings": bool(cfg.tie_embeddings),
        "num_heads": cfg.num_heads,
        "num_kv_heads": cfg.num_kv_heads or cfg.num_heads,
        "head_dim": cfg.head_dim,
        "remat": bool(cfg.remat),
    }
    return TransformerLM(cfg), shape
