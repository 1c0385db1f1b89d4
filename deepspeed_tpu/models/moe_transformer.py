"""MoE decoder model family.

Counterpart of the reference's MoE model usage (``deepspeed/moe/layer.py``
``MoE`` wrapping each FFN; tests/unit/simple_model.py ``SimpleMoEModel``/
``SimplePRMoEModel``): a ``TransformerLM`` whose MLP blocks are Mixture-of-
Experts layers dispatched over the ``expert`` mesh axis.

TPU-shaping: when every layer is MoE (``moe_layer_freq == 1``) the expert
weights stack as ``[L, E, ...]`` and the block still runs under ``lax.scan``;
with interleaved dense/MoE layers the loop unrolls (two param stacks).
The load-balance aux loss is scaled by ``moe_aux_loss_coef`` at the layer and
accumulated through the scan carry into the training loss.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.config import TransformerConfig
from deepspeed_tpu.models.transformer import TransformerLM
from deepspeed_tpu.moe.layer import MoE


@dataclasses.dataclass
class MoETransformerConfig(TransformerConfig):
    num_experts: int = 8
    moe_layer_freq: int = 1  # every k-th layer is MoE (reference "ep_interval")
    moe_top_k: int = 1
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    min_capacity: int = 4
    use_residual: bool = False  # PR-MoE
    noisy_gate_policy: Optional[str] = None  # None | 'RSample' | 'Jitter'
    # True: capacity routing (top-1 / top-2 only; tokens over an expert's
    # capacity are dropped). False: dropless, any k: tokens sorted by expert
    # and multiplied group by group (moe/routed_ffn.py); not expert-parallel yet
    moe_drop_tokens: bool = True
    # dropless routing: renormalise the k chosen gates to sum to one. None: as
    # the capacity gates do (top-1 keeps the plain gate, k > 1 renormalises;
    # moe/routed_ffn.py::route). OLMoE: False
    moe_norm_topk_prob: Optional[bool] = None
    moe_use_rts: bool = True
    moe_aux_loss_coef: float = 0.01
    expert_intermediate_size: Optional[int] = None
    # int8 wire format for the expert-parallel dispatch/combine all-to-alls
    # (EQuARX-style per-chunk scales, moe/a2a.py:quantized_all_to_all)
    moe_quantized_a2a: bool = False

    def __post_init__(self):
        super().__post_init__()
        if self.num_loops > 1 or self.post_sublayer_norm:
            raise NotImplementedError(
                "num_loops > 1 and post_sublayer_norm are the dense TransformerLM's: a routed model's serving step "
                "hands back routing counts a layer of WEIGHTS ([num_layers, num_experts], decode.py::_moe_stat_rows) "
                "and its expert stacks are reached through a layer offset; neither knows of a pass"
            )
        if self.expert_intermediate_size is None:
            self.expert_intermediate_size = self.intermediate_size
        if self.moe_top_k > 2 and self.moe_drop_tokens:
            raise ValueError(
                f"moe_top_k={self.moe_top_k} needs moe_drop_tokens=False: capacity routing "
                "(moe_drop_tokens=True) supports top-1 and top-2 only"
            )
        if self.moe_layer_freq > 1:
            # mixed dense/MoE stacks can't share one scanned param stack
            self.scan_layers = False


class MoETransformerLM(TransformerLM):
    def __init__(self, config: MoETransformerConfig):
        super().__init__(config)
        cfg = config
        self.moe = MoE(
            hidden_size=cfg.hidden_size,
            num_experts=cfg.num_experts,
            k=cfg.moe_top_k,
            capacity_factor=cfg.capacity_factor,
            eval_capacity_factor=cfg.eval_capacity_factor,
            min_capacity=cfg.min_capacity,
            use_residual=cfg.use_residual,
            noisy_gate_policy=cfg.noisy_gate_policy,
            drop_tokens=cfg.moe_drop_tokens,
            use_rts=cfg.moe_use_rts,
            intermediate_size=cfg.expert_intermediate_size,
            activation=cfg.activation if cfg.activation in ("gelu", "relu", "swiglu", "geglu") else "gelu",
            use_bias=cfg.use_bias,
            out_std=0.02 / np.sqrt(2 * cfg.num_layers),
            quantized_a2a=cfg.moe_quantized_a2a,
            norm_topk_prob=cfg.moe_norm_topk_prob,
        )
        moe_layers = [i for i in range(cfg.num_layers) if self._is_moe_layer(i)]
        dense_layers = [i for i in range(cfg.num_layers) if not self._is_moe_layer(i)]
        self._moe_index = {li: j for j, li in enumerate(moe_layers)}
        self._dense_index = {li: j for j, li in enumerate(dense_layers)}

    def _is_moe_layer(self, i: int) -> bool:
        return (i + 1) % self.config.moe_layer_freq == 0

    def stream_fns(self):
        raise NotImplementedError(
            "offload_param layer streaming does not support MoE families: the "
            "expert params live outside the stacked layer tree and the "
            "load-balance aux loss cannot ride the per-layer stream programs"
        )

    # --- params ---------------------------------------------------------
    def init(self, rng, batch) -> Dict[str, Any]:
        cfg = self.config
        rng, moe_rng = jax.random.split(rng)
        params = super().init(rng, batch)
        L = cfg.num_layers
        moe_layers = [i for i in range(L) if self._is_moe_layer(i)]
        dense_mlp_keys = {"w_in", "b_in", "w_gate", "w_up", "w_out", "b_out"}
        present = dense_mlp_keys & set(params["layers"])
        if cfg.moe_layer_freq == 1:
            # every layer is MoE: drop the dense FFN stack, scan over [L, E, ...]
            for key in present:
                del params["layers"][key]
            keys = jax.random.split(moe_rng, L)
            params["layers"]["moe"] = jax.vmap(self.moe.init)(keys)
        else:
            # interleaved: dense FFN weights restack over dense layers only
            # ([L_dense, ...]) so MoE layers carry no dead dense params
            dense_idx = np.asarray([i for i in range(L) if i not in set(moe_layers)])
            params["dense_mlp"] = {k: params["layers"].pop(k)[dense_idx] for k in present}
            keys = jax.random.split(moe_rng, len(moe_layers))
            params["moe_layers"] = jax.vmap(self.moe.init)(keys)
        return params

    def _layer_params(self, params, i: int):
        """Unrolled path (moe_layer_freq > 1): merge the layer's attention
        stack slice with its dense-FFN or MoE params by layer index."""
        per_layer = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        if self.config.moe_layer_freq == 1:
            return per_layer
        if self._is_moe_layer(i):
            j = self._moe_index[i]
            per_layer["moe"] = jax.tree_util.tree_map(lambda a: a[j], params["moe_layers"])
        else:
            j = self._dense_index[i]
            for k, v in params["dense_mlp"].items():
                per_layer[k] = v[j]
        return per_layer

    # --- sharding -------------------------------------------------------
    def tp_partition_rules(self, params_shapes=None) -> Any:
        if params_shapes is None:
            return None
        base = super().tp_partition_rules(params_shapes)

        def moe_rules(stacked_moe_shapes):
            """Stacked [L?, E, ...] expert leaves → expert-axis specs."""

            def walk(prefix, tree):
                if isinstance(tree, dict):
                    return {k: walk(f"{prefix}/{k}", v) for k, v in tree.items()}
                nd = len(tree.shape)
                if prefix.startswith("/experts"):
                    # leading stack dim (scanned layer), then the expert dim
                    return P(None, "expert", *([None] * (nd - 2)))
                return P(*([None] * nd))

            return walk("", stacked_moe_shapes)

        if "moe" in params_shapes.get("layers", {}):
            base["layers"]["moe"] = moe_rules(params_shapes["layers"]["moe"])
        if "moe_layers" in params_shapes:
            base["moe_layers"] = moe_rules(params_shapes["moe_layers"])
        # dense_mlp (interleaved mode) already gets correct Megatron col/row
        # specs from the base name-driven walk — nothing to override.
        return base

    def keep_fp32_params(self, params_shapes=None) -> Any:
        """Router (gate) weights stay fp32 under mixed precision — the
        reference's TopKGate holds ``wg`` in fp32 for routing stability."""
        if params_shapes is None:
            return None

        def walk(prefix, tree):
            if isinstance(tree, dict):
                return {k: walk(f"{prefix}/{k}", v) for k, v in tree.items()}
            return prefix.endswith("/gate/wg")

        return walk("", params_shapes)

    # --- forward --------------------------------------------------------
    def _mlp(self, p, h, rng, train):
        cfg = self.config
        if "moe" in p:
            out, l_aux, _counts = self.moe.apply(p["moe"], h, train=train, rng=rng)
            return out, l_aux * jnp.float32(cfg.moe_aux_loss_coef)
        return super()._mlp(p, h, rng, train)

def moe_llama_config(size: str = "tiny", **overrides) -> MoETransformerConfig:
    """Llama body with 8 experts in every layer. Routing as the defaults give
    it: top-1 through the capacity path (``moe_drop_tokens=True``, tokens
    over capacity dropped); ``moe_drop_tokens=False`` takes the sorted,
    grouped path instead."""
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8, vocab_size=32000, max_seq_len=512),
        "1b-8e": dict(hidden_size=2048, num_layers=22, num_heads=32, num_kv_heads=4, vocab_size=32000),
    }
    base = dict(
        norm="rmsnorm",
        position="rope",
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
    )
    base.update(presets[size])
    base.update(overrides)
    return MoETransformerConfig(**base)


def mixtral_config(size: str = "8x7b", **overrides) -> MoETransformerConfig:
    """Mixtral presets (BASELINE config 5's model family): GQA llama body,
    8 experts, top-2 routing with the two gates renormalised, every layer
    MoE. As configured here it routes through the capacity path
    (``moe_drop_tokens=True``: tokens over ``capacity_factor`` are dropped,
    which the published model does not do); ``moe_drop_tokens=False`` gives
    the published dropless routing by the sorted, grouped path."""
    presets = {
        "tiny": dict(hidden_size=256, num_layers=4, num_heads=8, num_kv_heads=2, vocab_size=32000, max_seq_len=512),
        "8x7b": dict(
            hidden_size=4096,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            intermediate_size=14336,
            vocab_size=32000,
            max_seq_len=32768,
        ),
    }
    base = dict(
        norm="rmsnorm",
        position="rope",
        rope_theta=1e6,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        num_experts=8,
        moe_top_k=2,
        moe_layer_freq=1,
    )
    base.update(presets[size])
    base.update(overrides)
    return MoETransformerConfig(**base)


def olmoe_config(size: str = "1b-7b", **overrides) -> MoETransformerConfig:
    """OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct ``config.json``,
    ``model_type: olmoe``): 16 layers of MHA (16 heads of 128, RoPE 1e4,
    RMSNorm 1e-5) with QK-norm over the whole q and k projections, and in
    every layer 64 SwiGLU experts of width 1,024, 8 a token, no shared
    expert, softmax over all 64 and the top 8 NOT renormalised, no drops:
    the sorted, grouped path (``moe/routed_ffn.py``). Vocabulary 50,304,
    head untied, 4,096 positions. 6.92 B parameters, 1.3 B active."""
    presets = {
        "tiny": dict(hidden_size=64, num_layers=2, num_heads=4, intermediate_size=32, vocab_size=512,
                     max_seq_len=256, num_experts=8, moe_top_k=3),
        "1b-7b": dict(hidden_size=2048, num_layers=16, num_heads=16, num_kv_heads=16, intermediate_size=1024,
                      vocab_size=50304, max_seq_len=4096, num_experts=64, moe_top_k=8),
    }
    base = dict(
        norm="rmsnorm",
        norm_eps=1e-5,
        position="rope",
        rope_theta=10000.0,
        activation="swiglu",
        use_bias=False,
        tie_embeddings=False,
        qk_norm="projection",
        moe_layer_freq=1,
        moe_drop_tokens=False,
        moe_norm_topk_prob=False,
    )
    base.update(presets[size])
    base.update(overrides)
    return MoETransformerConfig(**base)
