"""Stacked expert FFNs.

Counterpart of ``deepspeed/moe/experts.py`` (``Experts`` — a ModuleList of
deep-copied expert modules, each rank holding ``num_local_experts``). The
TPU-native layout stacks every expert's weights on a leading ``[E, ...]`` dim
sharded over the ``expert`` mesh axis, so "local experts" are the shards XLA
assigns — expert compute is one batched einsum that lands on the MXU, and no
Python loop over experts exists.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def init_dense_ffn(
    rng,
    hidden_size: int,
    intermediate_size: int,
    activation: str = "gelu",
    use_bias: bool = True,
    std: float = 0.02,
    out_std: float = None,
) -> Dict[str, Any]:
    """Single dense FFN params (the MoE residual branch / per-layer MLP)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    H, I = hidden_size, intermediate_size
    out_std = std if out_std is None else out_std
    params: Dict[str, Any] = {}
    if activation in ("swiglu", "geglu"):
        params["w_gate"] = jax.random.normal(k1, (H, I), jnp.float32) * std
        params["w_up"] = jax.random.normal(k3, (H, I), jnp.float32) * std
    else:
        params["w_in"] = jax.random.normal(k1, (H, I), jnp.float32) * std
        if use_bias:
            params["b_in"] = jnp.zeros((I,))
    params["w_out"] = jax.random.normal(k2, (I, H), jnp.float32) * out_std
    if use_bias:
        params["b_out"] = jnp.zeros((H,))
    return params


# an FFN of two matrices, ``act(x w_in) w_out``: the one place that owns the pointwise activations
_POINTWISE = {
    "gelu": jax.nn.gelu,
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),  # Nemotron-H's ``relu2``: the rectified input, squared
    "quick_gelu": lambda x: x * jax.nn.sigmoid(1.702 * x),  # CLIP
}
POINTWISE_ACTIVATIONS = tuple(_POINTWISE)


def _pointwise_activation(x: jnp.ndarray, activation: str) -> jnp.ndarray:
    if activation not in _POINTWISE:
        raise ValueError(f"unknown pointwise activation {activation!r}")
    return _POINTWISE[activation](x)


def apply_dense_ffn(params: Dict[str, Any], x: jnp.ndarray, activation: str = "gelu",
                    tp=None, matmul=None) -> jnp.ndarray:
    """[..., H] → [..., H] dense FFN; single source of activation semantics
    (shared by TransformerLM layers and the PR-MoE residual branch).
    ``qmatmul`` fuses int8-weight dequantization when the leaves are
    quantized (``compression/int8.py``). Under tensor-parallel serving
    (``tp``, a ``inference/tp.py:TPServing`` inside shard_map) the up/gate
    projections are column-parallel (weights arrive pre-sliced), the down
    projection is row-parallel through ``tp.row_matmul``'s all-reduce, and
    the replicated output bias is added once, after the reduce.
    ``matmul(x, params, key)`` takes the place of ``qmatmul(x, params[key])``
    where the caller has its own (training's layers: ``models/transformer.py::_dense``)."""
    from deepspeed_tpu.compression.int8 import qmatmul

    if matmul is None:
        def matmul(x, params, key):
            return qmatmul(x, params[key])

    dt = x.dtype
    if activation in ("swiglu", "geglu"):
        gate = matmul(x, params, "w_gate")
        up = matmul(x, params, "w_up")
        act = jax.nn.silu(gate) if activation == "swiglu" else jax.nn.gelu(gate)
        inner = act * up
    else:
        inner = matmul(x, params, "w_in")
        if "b_in" in params:
            inner = inner + params["b_in"].astype(dt)
        inner = _pointwise_activation(inner, activation)
    out = (
        tp.row_matmul(inner, params["w_out"]) if tp is not None
        else matmul(inner, params, "w_out")
    ).astype(dt)
    if "b_out" in params:
        out = out + params["b_out"].astype(dt)
    return out


def init_expert_ffn(
    rng,
    num_experts: int,
    hidden_size: int,
    intermediate_size: int,
    activation: str = "gelu",
    use_bias: bool = True,
    std: float = 0.02,
    out_std: float = None,
) -> Dict[str, Any]:
    """Stacked expert MLP params: every leaf leads with the expert dim [E, ...]."""
    k1, k2, k3 = jax.random.split(rng, 3)
    E, H, I = num_experts, hidden_size, intermediate_size
    out_std = std if out_std is None else out_std
    params: Dict[str, Any] = {}
    if activation in ("swiglu", "geglu"):
        params["w_gate"] = jax.random.normal(k1, (E, H, I), jnp.float32) * std
        params["w_up"] = jax.random.normal(k3, (E, H, I), jnp.float32) * std
    else:
        params["w_in"] = jax.random.normal(k1, (E, H, I), jnp.float32) * std
        if use_bias:
            params["b_in"] = jnp.zeros((E, I))
    params["w_out"] = jax.random.normal(k2, (E, I, H), jnp.float32) * out_std
    if use_bias:
        params["b_out"] = jnp.zeros((E, H))
    return params


def apply_expert_ffn(params: Dict[str, Any], x: jnp.ndarray, activation: str = "gelu") -> jnp.ndarray:
    """[E, C, H] → [E, C, H]: each expert's FFN on its capacity slice.
    The batched ``x @ w`` contracts H per expert (einsum ``ech,ehi->eci``);
    ``qmatmul`` fuses int8-weight dequantization when the stacked leaves
    are quantized — its ``[E, 1, I]`` per-output-channel scales broadcast
    over the capacity dim — so MoE serving rides the same int8 weights as
    the dense path."""
    from deepspeed_tpu.compression.int8 import qmatmul

    dt = x.dtype
    if activation in ("swiglu", "geglu"):
        gate = qmatmul(x, params["w_gate"])
        up = qmatmul(x, params["w_up"])
        act = jax.nn.silu(gate) if activation == "swiglu" else jax.nn.gelu(gate)
        inner = act * up
    else:
        inner = qmatmul(x, params["w_in"])
        if "b_in" in params:
            inner = inner + params["b_in"][:, None, :].astype(dt)
        inner = _pointwise_activation(inner, activation)
    out = qmatmul(inner, params["w_out"]).astype(dt)
    if "b_out" in params:
        out = out + params["b_out"][:, None, :].astype(dt)
    return out


def expert_partition_rules(params: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpecs putting the stacked expert dim on the ``expert`` mesh
    axis (the reference's expert-parallel group, groups.py:113); remaining
    dims left for the ZeRO partitioner / TP to extend."""
    return jax.tree_util.tree_map(
        lambda p: P(*(("expert",) + (None,) * (np.ndim(p) - 1))), params
    )
