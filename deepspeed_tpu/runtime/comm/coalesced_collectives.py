"""ZeRO++ quantized / coalesced collectives.

Counterpart of the reference's ``deepspeed/runtime/comm/coalesced_collectives.py``:
``all_to_all_quant_reduce`` (:31 — 4-bit intra-node all-to-all then
inter-node reduce) and ``reduce_scatter_coalesced`` (:87). On TPU the
collectives are expressed inside ``shard_map`` so the quantization happens
*before* bytes hit the ICI:

* ``reduce_scatter_coalesced``  — stacked tensors, one fused psum_scatter;
* ``quantized_reduce_scatter``  — int8 block-quantized all-to-all + local
  reduction (qgZ): each chip sends only its peers' int8 shards + scales,
  cutting gradient-sync bandwidth 4× vs fp32 / 2× vs bf16;
* ``quantized_all_gather``      — int8 weight gather (qwZ) for ZeRO-3
  param gathers.

Both quantized ops are error-free in exact arithmetic only for the scales'
dynamic range — like the reference, they trade a small quantization error
for bandwidth; tests bound the error against the exact collective.
"""

from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from deepspeed_tpu.ops.quantizer import dequantize, quantize


def reduce_scatter_coalesced(
    tensors: Sequence[jnp.ndarray], mesh: Mesh, axis_name: str = "data"
) -> List[jnp.ndarray]:
    """Reduce-scatter a list of tensors in ONE collective (reference :87):
    flatten + concat, single psum_scatter over the axis, split back. Each
    returned tensor is the caller's 1/world shard of the sum."""
    world = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    flats = [t.reshape(-1) for t in tensors]
    sizes = [f.shape[0] for f in flats]
    padded = [
        jnp.pad(f, (0, (-f.shape[0]) % world)) for f in flats
    ]
    buf = jnp.concatenate(padded)

    def body(x):
        # x: this chip's full contribution; each chip keeps its reduced shard
        return jax.lax.psum_scatter(x, axis_name, tiled=True)

    out = shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=P(axis_name), check_vma=False
    )(buf)
    # out is the global scattered array; split per input
    shards = []
    off = 0
    for f, size in zip(padded, sizes):
        n = f.shape[0]
        shards.append(out[off : off + n][: size])
        off += n
    return shards


def quant_a2a_reduce_local(
    flat: jnp.ndarray, axis_name: str, world: int, gpg: int, num_bits: int
) -> jnp.ndarray:
    """Inside ``shard_map``: quantize this chip's contribution per destination
    chunk, all-to-all the int8 payload + scales, dequantize and sum — the qgZ
    wire pattern shared by ``quantized_reduce_scatter`` and the ZeRO++ grad
    path. ``flat`` [n] with n divisible by world×gpg; returns this chip's
    summed chunk [n/world] in fp32."""
    n = flat.shape[0]
    q, scale = quantize(flat.reshape(world, n // world), world * gpg, num_bits)
    q = q.reshape(world, gpg, -1)
    scale = scale.reshape(world, gpg)
    q_recv = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=False)
    s_recv = jax.lax.all_to_all(scale, axis_name, split_axis=0, concat_axis=0, tiled=False)
    deq = q_recv.astype(jnp.float32) * s_recv[..., None]
    return jnp.sum(deq, axis=0).reshape(-1)


def quant_all_gather_local(
    x: jnp.ndarray, axis_name: str, num_groups: int, num_bits: int
) -> jnp.ndarray:
    """Inside ``shard_map``: quantize the local array, all-gather int8 +
    scales, dequantize — the qwZ wire pattern shared by
    ``quantized_all_gather`` and the ZeRO++ param gathers. Returns
    [world, x.size] fp32 (one dequantized row per source chip)."""
    q, scale = quantize(x, num_groups, num_bits)
    qg = jax.lax.all_gather(q, axis_name, axis=0, tiled=False)
    sg = jax.lax.all_gather(scale, axis_name, axis=0, tiled=False)
    world = qg.shape[0]
    deq = qg.astype(jnp.float32) * sg[..., None]
    return deq.reshape(world, x.size)


def quantized_reduce_scatter(
    tensor: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "data",
    num_bits: int = 8,
    groups_per_shard: int = 16,
) -> jnp.ndarray:
    """qgZ (reference ``all_to_all_quant_reduce``): each chip quantizes its
    contribution per destination shard, all-to-alls the int8 payload +
    scales, and reduces the dequantized shards locally. Returns the global
    array whose shard s holds sum_over_chips(chunk_s)."""
    world = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    flat = tensor.reshape(-1)
    pad = (-flat.shape[0]) % (world * groups_per_shard)
    flat = jnp.pad(flat, (0, pad))
    n = flat.shape[0]

    def body(x):
        # x: this chip's full local copy [n] (replicated input)
        return quant_a2a_reduce_local(
            x, axis_name, world, groups_per_shard, num_bits
        ).reshape(1, n // world)

    out = shard_map(
        body,
        mesh=mesh,
        in_specs=P(),
        out_specs=P(axis_name),
        check_vma=False,
    )(flat)
    return out.reshape(-1)[: tensor.size + pad][: tensor.size] if pad else out.reshape(-1)


def quantized_all_gather(
    shard: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "data",
    num_bits: int = 8,
    num_groups: int = 16,
) -> jnp.ndarray:
    """qwZ (reference partition_parameters.py:654 quantized all-gather):
    each chip quantizes its local shard, gathers int8 + scales, dequantizes.
    ``shard`` is a global array sharded over ``axis_name`` dim 0."""

    def body(x):
        # x: local shard
        return quant_all_gather_local(x, axis_name, num_groups, num_bits).reshape(-1)

    local_shape = (shard.shape[0],) + shard.shape[1:]
    out = shard_map(
        body, mesh=mesh, in_specs=P(axis_name), out_specs=P(), check_vma=False
    )(shard.reshape(shard.shape[0], -1))
    return out.reshape((-1,) + shard.shape[1:])


def all_to_all_quant_reduce(tensors, mesh: Mesh, axis_name: str = "data", **kw):
    """Reference-named entry (``coalesced_collectives.py:31``): quantized
    grad reduce over a tensor list; each result is the caller's summed
    shard."""
    return [quantized_reduce_scatter(t, mesh, axis_name, **kw) for t in tensors]
