"""The dropless top-k routed FFN: tokens in expert order, experts
multiplied group by group.

This is what ``moe_drop_tokens=False`` means, for training
(``moe/layer.py::MoE.apply`` without an expert mesh axis) and for serving
(``inference/decode.py``) alike. The capacity path of ``sharded_moe.py``
reaches its experts through an ``[S, E, C]`` mask; without drops C = S, and
at 64 experts with 8 a token that mask alone is larger than the model's
work. Here every (token, expert) assignment is one row:

1. ``route``: softmax over ALL experts in float32, the k largest, their
   gates renormalised to one only where the model says so
   (``norm_topk_prob``: Mixtral-style presets yes, OLMoE no);
2. the ``S k`` assignments are put in expert order (a token's rows keep
   their order): on a TPU by COUNTING, steps 1 and 2 in one kernel call
   (``moe/route_plan.py``, ``moe_route_plan``, up to a token tile), elsewhere
   by two stable sorts; an assignment of
   a dead token (``live`` false: a padding slot of a serving window) lies
   behind every expert and belongs to no group, so it costs no expert work;
3. the sorted rows are built from their tokens (``moe/live_rows.py``,
   ``dispatch``): where the plan is the kernel's, only the ``sum(counts)``
   rows of the groups (``moe_dispatch_rows``), elsewhere all ``S k`` by a
   gather;
4. gate/up, activation and down projection run as grouped matmuls over the
   ``[E, H, I]`` stacks with the group sizes as data
   (``moe/grouped_matmul.py``): a shifting routing mix compiles nothing;
5. each row goes back to its token and the token's rows are summed with
   their gates in float32 (``live_rows.combine``): the rows of the groups
   alone, added where their tokens lie (``moe_combine_rows``), or all
   ``S k`` gathered, masked and summed in k slabs.

Returns the output and the per-expert assignment counts (live tokens only).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.moe import live_rows
from deepspeed_tpu.moe.grouped_matmul import grouped_matmul
from deepspeed_tpu.moe.route_plan import plan_path, route_plan, scores, top_k_route


def route(logits: jnp.ndarray, k: int, norm_topk_prob: Optional[bool], select_logits: Optional[jnp.ndarray] = None,
          scoring: str = "softmax", select_bias: Optional[jnp.ndarray] = None):
    """``logits`` [S, E] float32 -> (gates of all experts [S, E], the chosen
    experts [S, k] int32, their gates [S, k]). ``norm_topk_prob`` None: as
    the capacity gates do (top-1 keeps the plain gate, k > 1 renormalises).
    ``select_logits`` (training noise) picks the experts; the gates always
    come from the clean logits. ``scoring`` ``sigmoid``: each expert's gate
    is its own sigmoid, not a share of a softmax. ``select_bias`` [E] is
    added to the gates for the choice alone (the load-balancing bias of
    sigmoid-routed models): the chosen gates are the unbiased ones."""
    E = logits.shape[-1]
    if not 1 <= k <= E:
        raise ValueError(f"top-k routing needs 1 <= k <= num_experts, got k={k} of {E}")
    return top_k_route(logits, k, k > 1 if norm_topk_prob is None else norm_topk_prob, select_logits, scoring, select_bias)


def _expert_matmul(rows, w, sizes, row_expert, group_offset, out_dtype, transposed: bool = False):
    """``grouped_matmul`` of sorted ``rows`` with the experts
    ``w[group_offset : group_offset + E]``. A stack may be int8
    (``compression/int8.py``: codes and per-output-channel scales
    ``[G, 1, N]``, applied to each row by its expert's). ``transposed``: the
    stack is ``[G, N, K]`` (``grouped_matmul``)."""
    from deepspeed_tpu.compression.int8 import QuantizedTensor

    if transposed:
        if isinstance(w, QuantizedTensor):
            raise NotImplementedError("an int8 expert stack stored by its output rows (w_in_t)")
        return grouped_matmul(rows, w.astype(rows.dtype), sizes, group_offset=group_offset, out_dtype=out_dtype, transposed=True)
    if isinstance(w, QuantizedTensor):
        # the codes are converted before the kernel, so only this call's experts are
        codes = jax.lax.dynamic_slice_in_dim(w.q, group_offset, sizes.shape[0], axis=0)
        out = grouped_matmul(rows, codes.astype(rows.dtype), sizes, out_dtype=jnp.float32)
        return (out * w.scale[:, 0, :].astype(jnp.float32)[row_expert]).astype(out_dtype)
    return grouped_matmul(rows, w.astype(rows.dtype), sizes, group_offset=group_offset, out_dtype=out_dtype)


def routed_ffn(
    experts: Dict[str, Any],
    tokens: jnp.ndarray,
    logits: jnp.ndarray,
    *,
    k: int,
    activation: str,
    norm_topk_prob: Optional[bool],
    live: Optional[jnp.ndarray] = None,
    select_logits: Optional[jnp.ndarray] = None,
    group_offset=0,
    scoring: str = "softmax",
    select_bias: Optional[jnp.ndarray] = None,
    held: Optional[Tuple[int, int]] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``tokens`` [S, H] through their k experts of the stacked ``experts``
    (``moe/experts.py::init_expert_ffn``'s leaves ``[E, ...]``). ``logits``
    [S, E] are the router's, float32. ``live`` [S] bool: tokens that are
    routed at all. The leaves may hold more experts than the router has
    (``[G, ...]``, every layer's experts in one stack): expert e is then
    ``leaf[group_offset + e]``, the offset being data, so that the kernel
    reads a layer's experts where they lie (sliced out of the stack first, a
    layer's 268 MB a matrix would be copied before each call).
    ``held`` ``(first, n)``: this chip's share of a deployment's experts. The
    router keeps its whole width E and its k a token, gates normalised over
    all k; the stacks hold experts ``first .. first + n`` only, an assignment
    to any other expert is dropped (it sorts behind every group and adds
    nothing: its chip adds it), and ``counts`` is ``[n]``, of the held alone.
    Returns ``(out [S, H] in tokens' dtype, counts [E] int32, gates [S, E])``."""
    from deepspeed_tpu.moe.experts import _pointwise_activation

    dt = tokens.dtype
    how = plan_path(*logits.shape, k)
    with jax.named_scope("moe_route"):
        plan = route_plan(
            logits, k=k, norm_topk_prob=norm_topk_prob, scoring=scoring, select_logits=select_logits,
            select_bias=select_bias, live=live, held=held, impl=how["path"],
        )
        gates = scores(logits, scoring)  # for the caller's auxiliary loss; nothing in a program that drops them
        counts = plan.counts
        row_expert = group_offset + plan.row_expert
        rows = live_rows.dispatch(tokens, plan, impl=how["combine"])
    with jax.named_scope("moe_experts"):
        if activation in ("swiglu", "geglu"):
            gate = _expert_matmul(rows, experts["w_gate"], counts, row_expert, group_offset, dt)
            up = _expert_matmul(rows, experts["w_up"], counts, row_expert, group_offset, dt)
            inner = (jax.nn.silu(gate) if activation == "swiglu" else jax.nn.gelu(gate)) * up
        else:
            if "w_in_t" in experts:  # the input matrix stored by its output rows, [E, I, H] (``grouped_matmul``'s ``transposed``)
                inner = _expert_matmul(rows, experts["w_in_t"], counts, row_expert, group_offset, dt, transposed=True)
            else:
                inner = _expert_matmul(rows, experts["w_in"], counts, row_expert, group_offset, dt)
            if "b_in" in experts:
                inner = inner + experts["b_in"].astype(dt)[row_expert]
            inner = _pointwise_activation(inner, activation)
        out_rows = _expert_matmul(inner, experts["w_out"], counts, row_expert, group_offset, jnp.float32)
        if "b_out" in experts:
            out_rows = out_rows + experts["b_out"].astype(jnp.float32)[row_expert]
    with jax.named_scope("moe_route"):
        # back to token order; the row of a dead or not-held assignment lies behind every group and was never computed
        out = live_rows.combine(out_rows, plan, dt, masked=held is not None or live is not None, impl=how["combine"])
    return out, counts, gates


def load_balance_loss(gates: jnp.ndarray, counts: jnp.ndarray, k: int, live: Optional[jnp.ndarray] = None):
    """``E sum_e (mean gate of e) (share of assignments that went to e)``:
    ``top1gating``'s auxiliary loss, with the share taken over all k choices."""
    E = gates.shape[-1]
    if live is None:
        n = jnp.float32(gates.shape[0])
        me = jnp.mean(gates, axis=0)
    else:
        n = jnp.maximum(jnp.sum(live.astype(jnp.float32)), 1.0)
        me = jnp.sum(gates * live[:, None].astype(gates.dtype), axis=0) / n
    return jnp.sum(me * counts.astype(jnp.float32) / (n * k)) * E
