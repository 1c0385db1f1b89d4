"""ZeRO++ runtime wiring: qwZ / qgZ / hpZ.

Reference: ``deepspeed/runtime/zero/config.py:260-272`` (the three flags),
``partition_parameters.py:654`` (quantized weight all-gather, qwZ),
``partition_parameters.py:1490`` (secondary hpZ partition),
``runtime/comm/coalesced_collectives.py:31`` (quantized grad reduce, qgZ).

TPU-native mapping:

* **qwZ** (``zero_quantized_weights``) — the stage-3 param all-gather carries
  int8. Under GSPMD the gather is implicit, so the quantization is expressed
  as a *resharding boundary*: quantize shard-locally (per-group scales along
  the sharded dim), pin the int8 payload + scales sharded, re-pin them
  replicated — XLA inserts the all-gather **on the int8 arrays** — then
  dequantize. Gradients pass straight through (STE), and XLA's normal
  cotangent reduce-scatter is unchanged.
* **qgZ** (``zero_quantized_gradients``) — XLA's implicit grad reduce
  cannot be quantized (round() does not commute with psum), so the grad
  path switches to an explicit ``shard_map`` over the data axis: per-chip
  partial grads are block-quantized and all-to-all'd (1 int8 hop), then
  summed locally straight into the stage-2/3 scattered layout —
  ≈1 byte/element on the wire vs 2 for a bf16 reduce-scatter and 4 for
  fp32, the reference's 4× claim. Leaves whose accumulation buffer is
  replicated add one int8 all-gather of the sums.
* **hpZ** (``zero_hpz_partition_size``) — the bf16 param store (the gather
  source) is sharded only *within* a group of that size and replicated
  across groups, so gathers ride intra-group ICI; the fp32 master + moments
  stay sharded over the FULL data-parallel world (no optimizer memory is
  given back). Expressed as a data→(data, data_outer) mesh split where
  param specs use the inner axis and master/grad specs use both.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from deepspeed_tpu.ops.quantizer import quantize
from deepspeed_tpu.parallel.mesh import Topology
from deepspeed_tpu.runtime.comm.coalesced_collectives import (
    quant_a2a_reduce_local,
    quant_all_gather_local,
)

_TARGET_GROUP = 2048  # quant-group width target (reference default block)


def _group_count(n: int, target: int = _TARGET_GROUP) -> int:
    """Largest divisor-based group split of ``n`` with groups ≤ target."""
    k = max(1, -(-n // target))  # ceil
    while n % k:
        k += 1
    return k


# ---------------------------------------------------------------------------
# qwZ — int8 param-gather boundary (GSPMD path)
# ---------------------------------------------------------------------------
def _sharded_dim(spec: P, zero_axes) -> int:
    """Index of the dim carrying a ZeRO axis in ``spec``; -1 if none."""
    zset = set(zero_axes)
    for i, e in enumerate(spec):
        entries = e if isinstance(e, (tuple, list)) else (e,)
        if zset & {a for a in entries if a is not None}:
            return i
    return -1


def qwz_gather_tree(params: Any, spec_tree: Any, topo: Topology, num_bits: int = 8) -> Any:
    """Fake-quantized gather of every ZeRO-sharded leaf: the value handed to
    the model is dequantize(quantize(p)) and the wire format of the implicit
    all-gather is int8. Leaves without a ZeRO-sharded dim pass through."""
    mesh = topo.mesh
    zero_axes = topo.zero_shard_axes

    def leaf(p, spec):
        d = _sharded_dim(spec, zero_axes)
        if d < 0 or np.ndim(p) == 0:
            return p
        entry = list(spec)[d]

        @jax.custom_vjp
        def fq_gather(x):
            xt = jnp.moveaxis(x, d, 0)
            lead = xt.shape[0]
            rest = int(np.prod(xt.shape[1:])) if xt.ndim > 1 else 1
            k = _group_count(rest)
            flat = xt.reshape(lead * k, rest // k)
            q, s = quantize(flat, lead * k, num_bits)
            # computed shard-local…
            q = jax.lax.with_sharding_constraint(q, NamedSharding(mesh, P(entry, None)))
            s = jax.lax.with_sharding_constraint(s, NamedSharding(mesh, P(entry)))
            # …gathered as int8…
            q = jax.lax.with_sharding_constraint(q, NamedSharding(mesh, P(None, None)))
            s = jax.lax.with_sharding_constraint(s, NamedSharding(mesh, P(None)))
            # …dequantized replicated
            full = (q.astype(jnp.float32) * s[:, None]).reshape(xt.shape).astype(x.dtype)
            return jnp.moveaxis(full, 0, d)

        def fwd(x):
            return fq_gather(x), None

        def bwd(_, g):
            return (g,)  # STE: XLA reduce-scatters the cotangent as usual

        fq_gather.defvjp(fwd, bwd)
        return fq_gather(p)

    return jax.tree_util.tree_map(
        leaf, params, spec_tree, is_leaf=lambda x: isinstance(x, P)
    )


# ---------------------------------------------------------------------------
# qgZ — explicit quantized gradient all-reduce (shard_map path)
# ---------------------------------------------------------------------------
def validate_qgz_mesh(topo: Topology) -> None:
    bad = {
        ax: topo.axis_size(ax)
        for ax in ("model", "sequence", "expert", "pipe", "data_outer")
        if topo.axis_size(ax) > 1
    }
    if bad:
        raise ValueError(
            "zero_quantized_gradients runs the explicit data-parallel grad "
            f"reduce and supports a pure data-axis mesh; got non-trivial axes {bad}"
        )


def _quantized_reduce_leaf(
    g: jnp.ndarray, grad_spec: P, axis: str, world: int, num_bits: int
) -> jnp.ndarray:
    """Inside shard_map: reduce one partial-grad leaf across the data axis
    with int8 on the wire, averaging the per-chip contributions (each chip
    differentiates its LOCAL-batch mean; the exact path differentiates the
    global mean = sum/world).

    When the leaf's accumulation buffer is sharded (stage ≥ 2), the reduce is
    a pure scatter — one int8 all-to-all, each chip keeps only its own chunk.
    Replicated leaves (stage < 2 / sub-threshold) add an int8 all-gather hop."""
    shape, dtype = g.shape, g.dtype
    d = _sharded_dim(grad_spec, (axis,))
    if d >= 0 and shape[d] % world == 0:
        gt = jnp.moveaxis(g.astype(jnp.float32), d, 0)
        chunk = int(np.prod(gt.shape)) // world
        gpg = _group_count(chunk)
        flat = gt.reshape(-1)
        mine = quant_a2a_reduce_local(flat, axis, world, gpg, num_bits) / world
        local = mine.reshape((gt.shape[0] // world,) + gt.shape[1:])
        return jnp.moveaxis(local, 0, d).astype(dtype)
    # replicated output: scatter-reduce then int8 gather of the sums
    flat = g.astype(jnp.float32).reshape(-1)
    n0 = flat.shape[0]
    gpg = _group_count(max(1, -(-n0 // world)))
    pad = (-n0) % (world * gpg)
    flat = jnp.pad(flat, (0, pad))
    mine = quant_a2a_reduce_local(flat, axis, world, gpg, num_bits) / world
    full = quant_all_gather_local(mine, axis, gpg, num_bits).reshape(-1)
    return full[:n0].reshape(shape).astype(dtype)


def _gather_leaf_local(x_local, spec: P, axis: str, world: int, qwz: bool, num_bits: int):
    """Inside shard_map: materialize the full leaf from its local shard
    (int8 wire when qwZ is also enabled)."""
    d = _sharded_dim(spec, (axis,))
    if d < 0:
        return x_local
    if not qwz:
        return jax.lax.all_gather(x_local, axis, axis=d, tiled=True)
    xt = jnp.moveaxis(x_local, d, 0)
    lead, rest = xt.shape[0], int(np.prod(xt.shape[1:])) if xt.ndim > 1 else 1
    k = _group_count(rest)
    rows = quant_all_gather_local(
        xt.reshape(lead * k, max(1, rest // k)), axis, lead * k, num_bits
    )  # [world, local_size]
    full = rows.reshape((world * lead,) + xt.shape[1:])
    return jnp.moveaxis(full.astype(x_local.dtype), 0, d)


def build_qgz_fwd_bwd(
    loss_of: Callable,
    topo: Topology,
    param_spec_tree: Any,
    grad_spec_tree: Any,
    batch_spec_fn: Callable,
    qwz: bool,
    num_bits: int = 8,
) -> Callable:
    """fwd_bwd(params, grad_acc, scale, rng, batch) for the qgZ path.

    The loss/grad computation runs per chip inside ``shard_map``; sharded
    grad leaves cross the wire in ONE int8 all-to-all (≈1 byte/element vs 2
    for a bf16 reduce-scatter, 4 for fp32 — the reference's 4× claim) and
    land directly in the stage-2/3 scattered layout. Dropout rngs are shared
    across chips (each chip draws the same mask over its own rows) — parity
    tests run with dropout off, like the reference's qgZ tests."""
    mesh: Mesh = topo.mesh
    axis = "data"
    world = topo.axis_size(axis)
    is_spec = lambda v: isinstance(v, P)  # noqa: E731

    def fwd_bwd(params, grad_acc, scale, rng, batch):
        batch_specs = batch_spec_fn(batch)
        # a leaf's reduced grad leaves the shard_map in its accumulation
        # layout: the grad spec when the scatter applies, replicated otherwise
        def out_spec_of(p, sp):
            d = _sharded_dim(sp, (axis,))
            if d >= 0 and np.shape(p)[d] % world == 0:
                return sp
            return P()

        grad_out_specs = jax.tree_util.tree_map(
            out_spec_of, params, grad_spec_tree, is_leaf=is_spec
        )

        def body(p_shards, scale_, rng_, b_local):
            full = jax.tree_util.tree_map(
                lambda x, sp: _gather_leaf_local(x, sp, axis, world, qwz, num_bits),
                p_shards,
                param_spec_tree,
                is_leaf=is_spec,
            )

            def scaled_loss(f):
                return loss_of(f, b_local, rng_) * scale_.astype(jnp.float32)

            loss_local, g = jax.value_and_grad(scaled_loss)(full)
            g = jax.tree_util.tree_map(
                lambda t, sp: _quantized_reduce_leaf(t, sp, axis, world, num_bits),
                g,
                grad_spec_tree,
                is_leaf=is_spec,
            )
            return jax.lax.pmean(loss_local, axis), g

        loss_scaled, grads = shard_map(
            body,
            mesh=mesh,
            in_specs=(param_spec_tree, P(), P(), batch_specs),
            out_specs=(P(), grad_out_specs),
            check_vma=False,
        )(params, scale, rng, batch)
        new_acc = jax.tree_util.tree_map(
            lambda a, g, sp: jax.lax.with_sharding_constraint(
                a + g.astype(jnp.float32), NamedSharding(mesh, sp)
            ),
            grad_acc,
            grads,
            grad_spec_tree,
            is_leaf=is_spec,
        )
        return loss_scaled / scale.astype(jnp.float32), new_acc

    return fwd_bwd


# ---------------------------------------------------------------------------
# hpZ — secondary param partition via the data→(data, data_outer) split
# ---------------------------------------------------------------------------
def apply_hpz_mesh(mesh_config, zero_config, n_devices: int) -> None:
    """Split the data axis so params shard over groups of
    ``zero_hpz_partition_size`` (inner ``data``) and replicate across groups
    (``data_outer``); the partitioner keeps master/grads on the full DP world
    (``ZeroPartitioner`` hpZ branch)."""
    hpz = int(zero_config.zero_hpz_partition_size or 1)
    if hpz <= 1:
        return
    if zero_config.mics_shard_size and zero_config.mics_shard_size > 0:
        raise ValueError(
            "zero_hpz_partition_size and mics_shard_size both split the data "
            "axis and cannot be combined"
        )
    from deepspeed_tpu.runtime.config import split_data_axis

    split_data_axis(mesh_config, hpz, n_devices, "zero_hpz_partition_size")
