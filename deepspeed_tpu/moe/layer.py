"""MoE layer: gate + sharded experts (+ PR-MoE residual).

Counterpart of ``deepspeed/moe/layer.py`` (``MoE`` :16) and the ``MOELayer``
/ ``TopKGate`` pair (``deepspeed/moe/sharded_moe.py:435,:370``). The
reference binds experts to an expert-parallel process group created lazily in
``set_deepspeed_parallelism`` (layer.py:87); here expert placement is the
``expert`` mesh axis: the stacked ``[E, ...]`` expert weights and the
dispatched ``[E, C, H]`` activations both carry an ``expert``-axis sharding
constraint, and GSPMD materializes the reference's ``_AllToAll`` exchange
(sharded_moe.py:98) as XLA all-to-alls over ICI.

``use_residual=True`` gives PR-MoE (pyramid-residual, layer.py use_residual
branch): a dense MLP runs in parallel and a learned 2-way coefficient mixes
both outputs.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.moe import a2a, routed_ffn, sharded_moe
from deepspeed_tpu.moe.experts import (
    apply_dense_ffn,
    apply_expert_ffn,
    expert_partition_rules,
    init_dense_ffn,
    init_expert_ffn,
)


def routed_experts(
    experts: Dict[str, Any],
    tokens: jnp.ndarray,
    logits: jnp.ndarray,
    *,
    k: int,
    activation: str,
    drop_tokens: bool,
    norm_topk_prob: Optional[bool],
    capacity_factor: float,
    min_capacity: int,
    use_rts: bool = True,
    live: Optional[jnp.ndarray] = None,
    rng: Optional[jax.Array] = None,
    noisy_gate_policy: Optional[str] = None,
    constrain=lambda x, spec: x,
    group_offset=0,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The routed FFN on one expert group: ``tokens`` [S, H] with router
    ``logits`` [S, E] (float32) through the stacked ``experts``; ``live`` [S]
    marks the tokens that are routed at all (a serving window's dead slots
    are not). The ONE routing function of training (``MoE.apply``) and of
    serving (``inference/decode.py``); only ``MoE.apply``'s explicit
    all-to-all fast path over an ``expert`` mesh axis goes round it.

    ``drop_tokens=False``: the sorted, grouped path, any k
    (``moe/routed_ffn.py``). ``drop_tokens=True``: the capacity gates and
    their dispatch / combine einsums, k of 1 or 2 (``sharded_moe.py``).
    ``group_offset`` (dropless only): where the E experts begin in a longer
    stack (``routed_ffn``).
    Returns ``(out [S, H], l_aux, counts [E])``."""
    if not drop_tokens:
        noisy = None
        if noisy_gate_policy == "RSample" and rng is not None:
            noisy = logits + sharded_moe.gumbel_rsample(logits.shape, rng)
        out, counts, gates = routed_ffn.routed_ffn(
            experts, tokens, logits, k=k, activation=activation,
            norm_topk_prob=norm_topk_prob, live=live, select_logits=noisy, group_offset=group_offset,
        )
        return out, routed_ffn.load_balance_loss(gates, counts, k, live), counts
    l_aux, combine_w, dispatch_m, counts = sharded_moe.topkgating(
        logits,
        k,
        capacity_factor,
        min_capacity,
        drop_tokens=True,
        rng=rng,
        noisy_gate_policy=noisy_gate_policy,
        use_rts=use_rts,
        used_token_mask=live,
    )
    dispatched = constrain(sharded_moe.dispatch(tokens, dispatch_m), P("expert", None, None))
    expert_out = constrain(apply_expert_ffn(experts, dispatched, activation), P("expert", None, None))
    return sharded_moe.combine(expert_out, combine_w), l_aux, counts


def residual_mix(params: Dict[str, Any], tokens: jnp.ndarray, out: jnp.ndarray, activation: str) -> jnp.ndarray:
    """PR-MoE: a dense MLP beside the experts, mixed by a learned 2-way
    coefficient; ``out`` unchanged for a layer without one."""
    if "mlp" not in params:
        return out
    mlp_out = apply_dense_ffn(params["mlp"], tokens, activation)
    coef = tokens.astype(jnp.float32) @ params["coefficient"]["w"] + params["coefficient"]["b"]
    coef = jax.nn.softmax(coef, axis=-1).astype(out.dtype)
    return out * coef[..., 0:1] + mlp_out * coef[..., 1:2]


class MoE:
    """Mixture of Experts layer (functional).

    ``init(rng)`` builds the param tree; ``apply(params, x, ...)`` returns
    ``(output, l_aux, exp_counts)`` exactly like the reference's
    ``MoE.forward`` (layer.py:115).
    """

    def __init__(
        self,
        hidden_size: int,
        num_experts: int = 1,
        ep_size: int = 1,
        k: int = 1,
        capacity_factor: float = 1.0,
        eval_capacity_factor: float = 1.0,
        min_capacity: int = 4,
        use_residual: bool = False,
        noisy_gate_policy: Optional[str] = None,
        drop_tokens: bool = True,
        use_rts: bool = True,
        intermediate_size: Optional[int] = None,
        activation: str = "gelu",
        use_bias: bool = True,
        out_std: Optional[float] = None,
        quantized_a2a: bool = False,
        norm_topk_prob: Optional[bool] = None,
    ):
        self.hidden_size = hidden_size
        self.num_experts = num_experts
        # ep_size is accepted for reference-API parity (layer.py:16) but expert
        # placement is mesh-driven here: the 'expert' axis of the device mesh
        # (config "mesh": {"expert": N}) decides the parallel degree.
        self.ep_size = ep_size
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.min_capacity = min_capacity
        self.use_residual = use_residual
        self.noisy_gate_policy = noisy_gate_policy
        self.drop_tokens = drop_tokens
        self.use_rts = use_rts
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.activation = activation
        self.use_bias = use_bias
        self.out_std = out_std
        # int8 dispatch/combine wire format (EQuARX-style); an active
        # OverlapPlan's a2a stage overrides this layer-local default
        self.quantized_a2a = quantized_a2a
        # dropless routing: whether the k chosen gates are renormalised to sum
        # to one. None: as the capacity gates do (routed_ffn.route); OLMoE: False
        self.norm_topk_prob = norm_topk_prob

    # --- params ---------------------------------------------------------
    def init(self, rng) -> Dict[str, Any]:
        kg, ke, km, kc = jax.random.split(rng, 4)
        params: Dict[str, Any] = {
            # gate weight is fp32 always (reference TopKGate keeps wg in fp32)
            "gate": {"wg": jax.random.normal(kg, (self.hidden_size, self.num_experts), jnp.float32) * 0.02},
            "experts": init_expert_ffn(
                ke,
                self.num_experts,
                self.hidden_size,
                self.intermediate_size,
                activation=self.activation,
                use_bias=self.use_bias,
                out_std=self.out_std,
            ),
        }
        if self.use_residual:
            H = self.hidden_size
            params["mlp"] = init_dense_ffn(
                km,
                H,
                self.intermediate_size,
                activation=self.activation,
                use_bias=self.use_bias,
                out_std=self.out_std,
            )
            params["coefficient"] = {
                "w": jax.random.normal(kc, (H, 2), jnp.float32) * 0.02,
                "b": jnp.zeros((2,)),
            }
        return params

    # --- sharding -------------------------------------------------------
    def partition_rules(self, params: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Expert weights over the ``expert`` axis; gate/residual replicated."""
        if params is None:
            params = jax.eval_shape(lambda r: self.init(r), jax.random.PRNGKey(0))
        rules = jax.tree_util.tree_map(lambda p: P(*([None] * np.ndim(p))), params)
        rules["experts"] = expert_partition_rules(params["experts"])
        return rules

    def _constrain(self, x, spec):
        """Sharding constraint against the active topology (no-op off-mesh)."""
        from deepspeed_tpu.parallel.mesh import _TOPOLOGY

        if _TOPOLOGY is None or _TOPOLOGY.config.expert <= 1:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(_TOPOLOGY.mesh, spec))

    # --- forward --------------------------------------------------------
    def apply(
        self,
        params: Dict[str, Any],
        x: jnp.ndarray,
        *,
        train: bool = True,
        rng: Optional[jax.Array] = None,
        used_token_mask: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        orig_shape = x.shape
        H = orig_shape[-1]
        tokens = x.reshape(-1, H)

        gate_in = tokens
        if self.noisy_gate_policy == "Jitter" and train and rng is not None:
            rng, sub = jax.random.split(rng)
            gate_in = sharded_moe.multiplicative_jitter(tokens, sub)
        logits = gate_in.astype(jnp.float32) @ params["gate"]["wg"]

        cf = self.capacity_factor if train else self.eval_capacity_factor
        from deepspeed_tpu.parallel.mesh import _TOPOLOGY

        if not self.drop_tokens and _TOPOLOGY is not None and _TOPOLOGY.config.expert > 1:
            raise NotImplementedError(
                "dropless routing (drop_tokens=False) is not expert-parallel yet: the mesh's "
                f"'expert' axis is {_TOPOLOGY.config.expert}; use expert=1 or drop_tokens=True"
            )
        if self.drop_tokens and a2a.ep_fast_path(_TOPOLOGY, self.num_experts, tokens.shape[0]):
            # expert-parallel fast path: per-shard gating + explicit
            # dispatch/combine all-to-alls (moe/a2a.py). The dispatch a2a is
            # emitted before the residual/shared-dense branch and the combine
            # before the next layer's gating — both independent of that
            # compute, so the overlap pass finds real work to hide them
            # behind. Wire format comes from the engine's OverlapPlan a2a
            # stage when one is active (training trace), else the layer knob.
            from deepspeed_tpu.runtime.zero.overlap import active_plan

            plan = active_plan()
            quantized = (
                plan.a2a_quantized
                if plan is not None and plan.a2a_quantized is not None
                else self.quantized_a2a
            )
            dispatched, combine_w, l_aux_shards, count_shards = a2a.ep_gate_dispatch(
                tokens,
                logits,
                _TOPOLOGY,
                k=self.k,
                capacity_factor=cf,
                min_capacity=self.min_capacity,
                drop_tokens=self.drop_tokens,
                use_rts=self.use_rts,
                noisy_gate_policy=self.noisy_gate_policy if train else None,
                rng=rng if train else None,
                used_token_mask=used_token_mask,
                quantized=quantized,
            )
            rest = tuple(
                x for x in a2a.token_shard_axes(_TOPOLOGY) if x != "expert"
            )
            ep_spec = P("expert", rest if rest else None, None)
            expert_out = apply_expert_ffn(params["experts"], dispatched, self.activation)
            expert_out = self._constrain(expert_out, ep_spec)
            out = a2a.ep_combine(expert_out, combine_w, _TOPOLOGY, quantized=quantized)
            l_aux = jnp.mean(l_aux_shards)
            exp_counts = jnp.sum(count_shards, axis=0)
        else:
            live = None if used_token_mask is None else used_token_mask.reshape(-1).astype(bool)
            out, l_aux, exp_counts = routed_experts(
                params["experts"],
                tokens,
                logits,
                k=self.k,
                activation=self.activation,
                drop_tokens=self.drop_tokens,
                norm_topk_prob=self.norm_topk_prob,
                capacity_factor=cf,
                min_capacity=self.min_capacity,
                use_rts=self.use_rts,
                live=live,
                rng=rng if train else None,
                noisy_gate_policy=self.noisy_gate_policy if train else None,
                constrain=self._constrain,
            )

        out = residual_mix(params, tokens, out, self.activation)
        return out.reshape(orig_shape), l_aux, exp_counts
