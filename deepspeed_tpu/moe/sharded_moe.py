"""Top-k gating + capacity-based dispatch (TPU-native MoE core).

Counterpart of the reference's ``deepspeed/moe/sharded_moe.py`` (``top1gating``
:193, ``top2gating`` :290, ``MOELayer`` :435). The reference dispatches with
einsums and an explicit ``_AllToAll`` autograd function over the
expert-parallel process group (sharded_moe.py:98); here the dispatch/combine
einsums are identical, but the all-to-all is *implied*: the dispatched tensor
``[E, C, H]`` carries a sharding constraint putting dim 0 on the ``expert``
mesh axis while tokens arrive sharded over ``data`` — the XLA SPMD partitioner
inserts the all-to-all over ICI, and its inverse on combine. Differentiation
through the collective is automatic (no hand-written autograd function).

Everything is static-shaped for the MXU: capacity is a Python int derived
from token count, dropped tokens are masked (not ragged), and expert FFNs run
as one batched einsum over the stacked ``[E, ...]`` expert weights.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float, min_capacity: int) -> int:
    """Static tokens-per-expert capacity (reference sharded_moe.py:85)."""
    capacity = math.ceil(num_tokens / num_experts * capacity_factor)
    return max(capacity, min_capacity)


def multiplicative_jitter(x, rng, epsilon: float = 1e-2):
    """'Jitter' noisy gate policy (reference sharded_moe.py:106)."""
    if epsilon == 0 or rng is None:
        return x
    noise = jax.random.uniform(
        rng, x.shape, dtype=jnp.float32, minval=1.0 - epsilon, maxval=1.0 + epsilon
    )
    return x * noise.astype(x.dtype)


def gumbel_rsample(shape, rng):
    return jax.random.gumbel(rng, shape, dtype=jnp.float32)


def _one_hot(indices, num_classes):
    return jax.nn.one_hot(indices, num_classes, dtype=jnp.float32)


def _priority_locations(mask: jnp.ndarray, rng: Optional[jax.Array], use_rts: bool) -> jnp.ndarray:
    """Position of each token within its expert's queue, [S, E].

    Default priority is sequence order (cumsum). With Random Token Selection
    (``use_rts``, reference sharded_moe.py top1gating RTS branch) tokens are
    ranked by a random permutation so capacity drops are unbiased instead of
    biased against late positions.
    """
    S = mask.shape[0]
    if use_rts and rng is not None:
        perm = jax.random.permutation(rng, S)
        inv = jnp.argsort(perm)
        permuted = mask[perm]
        locations = (jnp.cumsum(permuted, axis=0) - permuted)[inv]
    else:
        locations = jnp.cumsum(mask, axis=0) - mask
    return locations


def top1gating(
    logits: jnp.ndarray,
    capacity_factor: float,
    min_capacity: int,
    used_token_mask: Optional[jnp.ndarray] = None,
    noisy_gate_policy: Optional[str] = None,
    drop_tokens: bool = True,
    use_rts: bool = True,
    rng: Optional[jax.Array] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-1 gating (reference ``top1gating`` sharded_moe.py:193).

    Args: ``logits`` [S, E] raw gate scores.
    Returns ``(l_aux, combine_weights [S,E,C], dispatch_mask [S,E,C], exp_counts [E])``.
    """
    S, E = logits.shape
    capacity = _capacity(S, E, capacity_factor, min_capacity)
    if not drop_tokens:
        capacity = S  # every token fits; no drops (reference drop_tokens=False path)

    logits32 = logits.astype(jnp.float32)
    if noisy_gate_policy == "RSample" and rng is not None:
        rng, sub = jax.random.split(rng)
        logits_w_noise = logits32 + gumbel_rsample(logits32.shape, sub)
    else:
        logits_w_noise = logits32

    gates = jax.nn.softmax(logits32, axis=1)
    indices1 = jnp.argmax(logits_w_noise, axis=1)
    mask1 = _one_hot(indices1, E)
    if used_token_mask is not None:
        mask1 = mask1 * used_token_mask[:, None].astype(mask1.dtype)

    # load-balance aux loss: E * <fraction routed> . <mean gate prob>
    # (reference sharded_moe.py l_aux = num_experts * sum(me * ce))
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    exp_counts = jnp.sum(mask1, axis=0).astype(jnp.int32)

    rng_rts = None
    if rng is not None:
        rng, rng_rts = jax.random.split(rng)
    locations1 = _priority_locations(mask1, rng_rts, use_rts and drop_tokens)
    mask1 = mask1 * (locations1 < capacity).astype(mask1.dtype)
    locations1_s = jnp.sum(locations1 * mask1, axis=1).astype(jnp.int32)

    gates1_s = jnp.sum(gates * mask1, axis=1)  # gate prob of kept assignment
    locations1_sc = _one_hot(locations1_s, capacity) * jnp.sum(mask1, axis=1, keepdims=True)
    combine_weights = gates1_s[:, None, None] * mask1[:, :, None] * locations1_sc[:, None, :]
    dispatch_mask = combine_weights > 0
    return l_aux, combine_weights, dispatch_mask, exp_counts


def top2gating(
    logits: jnp.ndarray,
    capacity_factor: float,
    min_capacity: int,
    drop_tokens: bool = True,
    top2_2nd_expert_sampling: bool = True,
    rng: Optional[jax.Array] = None,
    used_token_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Top-2 gating (reference ``top2gating`` sharded_moe.py:290)."""
    S, E = logits.shape
    capacity = _capacity(S, E, capacity_factor * 2.0, min_capacity)
    if not drop_tokens:
        capacity = S

    logits32 = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits32, axis=1)

    indices1 = jnp.argmax(gates, axis=1)
    mask1 = _one_hot(indices1, E)
    if used_token_mask is not None:
        mask1 = mask1 * used_token_mask[:, None].astype(mask1.dtype)

    second_logits = logits32
    if top2_2nd_expert_sampling and rng is not None:
        rng, sub = jax.random.split(rng)
        second_logits = logits32 + gumbel_rsample(logits32.shape, sub)
    masked_second = jnp.where(mask1 > 0, -jnp.inf, second_logits)
    indices2 = jnp.argmax(masked_second, axis=1)
    mask2 = _one_hot(indices2, E)
    if used_token_mask is not None:
        mask2 = mask2 * used_token_mask[:, None].astype(mask2.dtype)

    locations1 = jnp.cumsum(mask1, axis=0) - mask1
    # second choices queue behind all first choices (reference :321)
    locations2 = jnp.cumsum(mask2, axis=0) - mask2 + jnp.sum(mask1, axis=0, keepdims=True)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * E

    exp_counts = jnp.sum(mask1 + mask2, axis=0).astype(jnp.int32)

    mask1 = mask1 * (locations1 < capacity).astype(mask1.dtype)
    mask2 = mask2 * (locations2 < capacity).astype(mask2.dtype)
    locations1_s = jnp.sum(locations1 * mask1, axis=1).astype(jnp.int32)
    locations2_s = jnp.sum(locations2 * mask2, axis=1).astype(jnp.int32)

    gates1_s = jnp.sum(gates * mask1, axis=1)
    gates2_s = jnp.sum(gates * mask2, axis=1)
    denom = jnp.clip(gates1_s + gates2_s, min=jnp.finfo(jnp.float32).eps)
    gates1_s = gates1_s / denom
    gates2_s = gates2_s / denom

    locations1_sc = _one_hot(locations1_s, capacity) * jnp.sum(mask1, axis=1, keepdims=True)
    locations2_sc = _one_hot(locations2_s, capacity) * jnp.sum(mask2, axis=1, keepdims=True)
    combine1 = gates1_s[:, None, None] * mask1[:, :, None] * locations1_sc[:, None, :]
    combine2 = gates2_s[:, None, None] * mask2[:, :, None] * locations2_sc[:, None, :]
    combine_weights = combine1 + combine2
    dispatch_mask = combine_weights > 0
    return l_aux, combine_weights, dispatch_mask, exp_counts


def topkgating(
    logits: jnp.ndarray,
    k: int,
    capacity_factor: float,
    min_capacity: int,
    drop_tokens: bool = True,
    rng: Optional[jax.Array] = None,
    noisy_gate_policy: Optional[str] = None,
    use_rts: bool = True,
    used_token_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dispatch to the k-specific gate (reference TopKGate.forward :407)."""
    if k == 1:
        return top1gating(
            logits,
            capacity_factor,
            min_capacity,
            used_token_mask=used_token_mask,
            noisy_gate_policy=noisy_gate_policy,
            drop_tokens=drop_tokens,
            use_rts=use_rts,
            rng=rng,
        )
    if k == 2:
        # noisy_gate_policy maps onto top-2's 2nd-expert Gumbel sampling
        # (reference top2gating has no RSample/Jitter branch either)
        return top2gating(
            logits,
            capacity_factor,
            min_capacity,
            drop_tokens=drop_tokens,
            rng=rng,
            used_token_mask=used_token_mask,
            top2_2nd_expert_sampling=rng is not None,
        )
    raise ValueError(
        f"capacity routing (drop_tokens=True) supports top-1 and top-2 only (got k={k}); "
        "any k runs dropless: drop_tokens=False (moe_drop_tokens in the model config), moe/routed_ffn.py"
    )


def dispatch(tokens: jnp.ndarray, dispatch_mask: jnp.ndarray) -> jnp.ndarray:
    """[S, H] tokens → [E, C, H] expert inputs (reference einsum "sec,sm->ecm"
    sharded_moe.py:476)."""
    return jnp.einsum("sec,sh->ech", dispatch_mask.astype(tokens.dtype), tokens)


def combine(expert_out: jnp.ndarray, combine_weights: jnp.ndarray) -> jnp.ndarray:
    """[E, C, H] expert outputs → [S, H] (reference einsum "sec,ecm->sm" :497)."""
    return jnp.einsum("sec,ech->sh", combine_weights.astype(expert_out.dtype), expert_out)
