"""Expert-parallel all-to-all exchange: the MoE training fast path.

Counterpart of the reference's explicit ``_AllToAll`` autograd function
(``deepspeed/moe/sharded_moe.py:98``): each data-parallel rank gates its OWN
tokens against a LOCAL capacity, dispatches them into a ``[E, C_local, H]``
buffer, and one all-to-all over the expert group hands every expert its
slice. The earlier GSPMD formulation in this repo annotated the global
``[S, E, C]`` gating tensors instead and let the partitioner derive the
exchange — which it did, but only after involuntarily replicating the token
matrix (SPMD "full rematerialization" on the ``[S, E]`` masks), leaving
exposed loop all-gathers the overlap pass flags.

This module restores the reference dataflow with ``shard_map``:

* **Per-shard gating** — ``ep_gate_dispatch`` runs ``topkgating`` on each
  token shard independently (capacity = ``ceil(S_local/E · cf)``, exactly
  the reference's per-rank capacity), so the cumsum/one-hot bookkeeping is
  pure local math: zero collectives, no partitioner guesswork, and the
  capacity-overflow drop pattern is a deterministic function of each
  shard's tokens alone.
* **Explicit dispatch/combine a2a** — ``lax.all_to_all`` over the
  ``expert`` mesh axis splits the local ``[E, C_l, H]`` buffer's expert dim
  and concatenates the received capacity blocks:
  ``[E, C_l, H] ↔ [E/e, e·C_l, H]``. The transpose of an all-to-all is the
  inverse all-to-all, so autodiff gives the backward exchange for free.
* **Int8 wire format** — ``quantized_all_to_all`` sends the payload as int8
  codes with a per-(expert, slot) fp32 scale side-channel (EQuARX-style,
  arXiv 2506.17615; generalizes ``inference/tp.py:quantized_all_reduce``
  from all-reduce to a2a op kinds). The cotangent rides the inverse
  exchange in the same wire format, so both directions cost fp32/4 on the
  wire; the collectives analysis pass prices the int8 payload via its
  ``quantized_*`` fields.

Every differentiable ``shard_map`` input/output is fully device-varying
(token-sharded or expert-sharded — the gate weight matmul stays OUTSIDE in
GSPMD-land), so gradients are exact without replication bookkeeping. The
expert FFN also runs outside, on the globally ``[E, n·C_l, H]``-shaped
dispatched tensor: both einsum operands are expert-sharded on the stacked
dim, so the compute is local and the expert-weight gradient reduction rides
the engine's existing ZeRO machinery.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.moe import sharded_moe

EXPERT_AXIS = "expert"

_SCALE_FLOOR = 1e-30  # an all-zero chunk must not divide by zero


def token_shard_axes(topo) -> Tuple[str, ...]:
    """Mesh axes the flattened ``[S, H]`` token dim is sharded over: the
    dense batch axes (B) followed by ``sequence`` (T) — the row-major merge
    order of ``x.reshape(-1, H)`` on a ``[B, T, H]`` activation."""
    axes = [a for a in ("data_outer", "data", EXPERT_AXIS) if topo.axis_size(a) > 1]
    if topo.axis_size("sequence") > 1:
        axes.append("sequence")
    return tuple(axes)


def _spec_entry(axes: Tuple[str, ...]):
    if not axes:
        return None
    if len(axes) == 1:
        return axes[0]
    return tuple(axes)


def ep_fast_path(topo, num_experts: int, num_tokens: int) -> bool:
    """True when the shard_map expert-parallel path applies: a real expert
    mesh axis that divides the expert count, and token shards of equal
    size (static shapes inside shard_map need even divisibility)."""
    if topo is None:
        return False
    e = topo.axis_size(EXPERT_AXIS)
    if e <= 1 or num_experts % e:
        return False
    n = int(np.prod([topo.axis_size(a) for a in token_shard_axes(topo)]))
    return n > 1 and num_tokens % n == 0


# --- wire formats -----------------------------------------------------------


def _all_to_all(x, split_axis: int, concat_axis: int, axis_name: str = EXPERT_AXIS):
    return jax.lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def quantized_all_to_all(x, split_axis: int, concat_axis: int, axis_name: str = EXPERT_AXIS):
    """All-to-all with an int8 wire format (inside shard_map).

    Encode: per-chunk symmetric quantization over the trailing (hidden)
    dim — ``scale = max|chunk|/127`` — then TWO a2a ops: the int8 codes and
    the fp32 scale side-channel; decode on arrival. Wire cost is
    ``bytes/4 + bytes/H`` of the fp32 payload. Backward: the cotangent
    takes the INVERSE exchange in the same wire format (the reference's
    quantized-gradient-comm contract: lossy but symmetric), so training
    never moves an fp-width a2a payload.
    """
    return _qa2a(x, split_axis, concat_axis, axis_name)


def _qa2a(x, split_axis, concat_axis, axis_name):
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, _SCALE_FLOOR) / 127.0  # [E, C, 1] side-channel
    q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    qx = _all_to_all(q, split_axis, concat_axis, axis_name)
    sx = _all_to_all(scale, split_axis, concat_axis, axis_name)
    return (qx.astype(jnp.float32) * sx).astype(x.dtype)


def _qa2a_fwd(x, split_axis, concat_axis, axis_name):
    return _qa2a(x, split_axis, concat_axis, axis_name), None


def _qa2a_bwd(split_axis, concat_axis, axis_name, _res, g):
    # inverse exchange (swap split/concat), same int8 wire
    return (quantized_all_to_all(g, concat_axis, split_axis, axis_name),)


quantized_all_to_all.defvjp(_qa2a_fwd, _qa2a_bwd)


def exchange_shard(x, *, inverse: bool = False, quantized: bool = False,
                   axis_name: str = EXPERT_AXIS):
    """Per-shard expert exchange ``[E, C, H] ↔ [E/e, e·C, H]`` (call inside
    shard_map). ``inverse=False`` is dispatch (split experts, gather
    capacity); ``inverse=True`` is combine."""
    split, concat = (1, 0) if inverse else (0, 1)
    if quantized:
        return quantized_all_to_all(x, split, concat, axis_name)
    return _all_to_all(x, split, concat, axis_name)


# --- global-view wrappers ---------------------------------------------------


def ep_gate_dispatch(
    tokens,
    logits,
    topo,
    *,
    k: int,
    capacity_factor: float,
    min_capacity: int,
    drop_tokens: bool = True,
    use_rts: bool = True,
    noisy_gate_policy: Optional[str] = None,
    rng: Optional[jax.Array] = None,
    used_token_mask=None,
    quantized: bool = False,
):
    """Per-shard gating + capacity dispatch + the dispatch all-to-all.

    ``tokens [S, H]`` / ``logits [S, E]`` arrive token-sharded; returns

    * ``dispatched [E, n·C_l, H]`` — expert-sharded on dim 0 (each expert
      shard holds every token shard's capacity block for its experts),
    * ``combine_w [S, E, C_l]`` — token-sharded, consumed by
      :func:`ep_combine`,
    * ``l_aux [n]`` — one load-balance loss per token shard (mean them),
    * ``exp_counts [n, E]`` — per-shard routed-token counts (sum them).
    """
    mesh = topo.mesh
    tok_axes = token_shard_axes(topo)
    rest = tuple(a for a in tok_axes if a != EXPERT_AXIS)
    tok_e, rest_e = _spec_entry(tok_axes), _spec_entry(rest)
    n = int(np.prod([topo.axis_size(a) for a in tok_axes]))

    in_specs = [P(tok_e, None), P(tok_e, None)]
    args = [tokens, logits]
    has_rng = rng is not None
    if has_rng:
        # one independent key per token shard, passed as sharded DATA so
        # every shard_map input stays device-varying
        keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(n))
        in_specs.append(P(tok_e) if keys.ndim == 1 else P(tok_e, None))
        args.append(keys)
    has_mask = used_token_mask is not None
    if has_mask:
        in_specs.append(P(tok_e))
        args.append(used_token_mask)

    def body(tok_l, lg_l, *extra):
        i = 0
        key = None
        if has_rng:
            key = extra[0][0]
            i = 1
        mask_l = extra[i] if has_mask else None
        l_aux, cw, dm, counts = sharded_moe.topkgating(
            lg_l,
            k,
            capacity_factor,
            min_capacity,
            drop_tokens=drop_tokens,
            rng=key,
            noisy_gate_policy=noisy_gate_policy,
            use_rts=use_rts,
            used_token_mask=mask_l,
        )
        d = sharded_moe.dispatch(tok_l, dm)  # [E, C_l, H], local
        d = exchange_shard(d, quantized=quantized)  # the dispatch a2a
        return d, cw, l_aux[None], counts[None]

    out_specs = (
        P(EXPERT_AXIS, rest_e, None),
        P(tok_e, None, None),
        P(tok_e),
        P(tok_e, None),
    )
    return shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs), out_specs=out_specs, check_vma=False
    )(*args)


def ep_combine(expert_out, combine_w, topo, *, quantized: bool = False):
    """The combine all-to-all + weighted un-dispatch: ``expert_out
    [E, n·C_l, H]`` (expert-sharded) → ``[S, H]`` (token-sharded)."""
    mesh = topo.mesh
    tok_axes = token_shard_axes(topo)
    rest = tuple(a for a in tok_axes if a != EXPERT_AXIS)
    tok_e, rest_e = _spec_entry(tok_axes), _spec_entry(rest)

    def body(eo_l, cw_l):
        back = exchange_shard(eo_l, inverse=True, quantized=quantized)  # [E, C_l, H]
        return sharded_moe.combine(back, cw_l)

    return shard_map(
        body,
        mesh=mesh,
        in_specs=(P(EXPERT_AXIS, rest_e, None), P(tok_e, None, None)),
        out_specs=P(tok_e, None),
        check_vma=False,
    )(expert_out, combine_w)
