"""Ring attention over the ``sequence`` mesh axis.

Extension beyond reference parity (SURVEY §2.3: the reference has no
ring/context-parallel implementation — long context is Ulysses only). Ring
attention removes Ulysses' head-count ceiling (sp ≤ num_heads) by keeping
heads whole and rotating K/V shards around the ICI ring with ``ppermute``
while every device accumulates online-softmax partial results for its local
query block (Liu et al., "Ring Attention with Blockwise Transformers").

Written for ``shard_map`` over the ``sequence`` axis; ``ring_attention``
wraps itself in shard_map when given a mesh. The per-step local block runs
as one fp32 einsum — block sizes are seq_len/sp per device, so XLA tiles it
onto the MXU directly; each ppermute overlaps with the next block's compute
(XLA schedules the rotation concurrently since the permuted buffer is not
needed until the following iteration).

Causality is handled with global-position masks derived from
``lax.axis_index``: a device's q block i attends fully to kv blocks j < i,
causally within j == i, and skips j > i (the mask drives exp() to zero; the
accumulator's running max keeps it stable). Differentiable by construction
(unrolled over sp steps; ppermute transposes to the reverse permutation).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _ring_attention_local(q, k, v, *, axis_name: str, causal: bool, scale: float):
    """Body run per-device inside shard_map.

    q: [B, t, NH, D]; k/v: [B, t, NKV, D] with NH = G·NKV (GQA) — kv stays
    at NKV heads so each ppermute hop moves only the grouped-kv bytes.
    """
    sp = jax.lax.psum(1, axis_name)  # static: mesh axis size
    idx = jax.lax.axis_index(axis_name)
    B, t, NH, D = q.shape
    NKV = k.shape[2]
    G = NH // NKV
    qf = q.astype(jnp.float32).reshape(B, t, NKV, G, D)

    local_pos = jnp.arange(t, dtype=jnp.int32)
    q_pos = idx * t + local_pos  # global positions of this q block

    m = jnp.full((B, t, NKV, G), NEG_INF, jnp.float32)
    l = jnp.zeros((B, t, NKV, G), jnp.float32)
    acc = jnp.zeros((B, t, NKV, G, D), jnp.float32)

    perm = [(r, (r + 1) % sp) for r in range(sp)]

    k_cur, v_cur = k, v
    for step in range(sp):
        j = (idx - step) % sp  # whose kv block we hold this step
        s = jnp.einsum("btkgd,bskd->btkgs", qf, k_cur.astype(jnp.float32)) * scale
        if causal:
            kv_pos = j * t + local_pos
            mask = (q_pos[:, None] >= kv_pos[None, :])[None, :, None, None, :]  # [1,t,1,1,t]
            s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        # fully-masked rows keep m == NEG_INF; subtracting it from NEG_INF
        # scores must still yield exp(0)=...=0, so clamp the shift.
        shift = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(s - shift[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - shift))
        l = corr * l + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "btkgs,bskd->btkgd", p, v_cur.astype(jnp.float32)
        )
        m = m_new
        if step != sp - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l[..., None]).astype(q.dtype).reshape(B, t, NH, D)


def ring_attention(
    q,
    k,
    v,
    *,
    mesh=None,
    axis_name: str = "sequence",
    causal: bool = True,
    scale: Optional[float] = None,
    batch_axes=None,
    head_axes=None,
    in_shard_map: bool = False,
):
    """Ring attention for [B, T, N, D] q/k/v sequence-sharded over ``axis_name``.

    With ``in_shard_map=True`` the inputs are per-device local shards and the
    caller is already inside a shard_map over ``axis_name``. Otherwise global
    arrays are expected and this wraps the body in shard_map over ``mesh``
    (default: the global topology's mesh).
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    body = partial(_ring_attention_local, axis_name=axis_name, causal=causal, scale=float(scale))
    if in_shard_map:
        return body(q, k, v)

    if mesh is None:
        from deepspeed_tpu.parallel.mesh import get_topology

        mesh = get_topology().mesh
    spec = P(batch_axes, axis_name, head_axes, None)
    smap = partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return smap(body)(q, k, v)
