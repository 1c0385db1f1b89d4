"""Sparse gradient support for embedding tables.

Reference capability (``deepspeed/runtime/sparse_tensor.py:68`` +
``engine.py:2398-2465``): embedding gradients are converted to a
(values, indices) ``SparseTensor`` and the DP reduction all-gathers the
compact pairs instead of all-reducing the dense [vocab, hidden] table — a
bandwidth win whenever the batch touches far fewer rows than the table has.

TPU-native mechanism: the same math as a *declarative collective choice*.
``sparse_embedding_lookup`` is the plain gather on the forward; its custom
VJP computes the table cotangent inside a ``shard_map`` over the data axes —
each shard all-gathers every shard's (token-ids, row-cotangents) pairs (the
compact representation; wire bytes ≈ global_tokens × (hidden+1) × 4) and
scatter-adds them locally into one [vocab, hidden] buffer. The result is
bit-identical to the dense path's psum of per-shard scatter-adds, but the
interconnect never carries the dense table. ``SparseTensor`` itself is kept
as the host-side surface for parity with the reference API.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from jax import shard_map


class SparseTensor:
    """Compact (indices, values) view of a row-sparse dense tensor
    (reference ``runtime/sparse_tensor.py:68``)."""

    def __init__(self, indices, values, dense_size: Tuple[int, ...]):
        self.indices = jnp.asarray(indices)
        self.values = jnp.asarray(values)
        self.dense_size = tuple(dense_size)

    @staticmethod
    def from_dense(tensor, indices=None) -> "SparseTensor":
        t = jnp.asarray(tensor)
        if indices is None:
            row_mass = jnp.abs(t).sum(axis=tuple(range(1, t.ndim)))
            indices = jnp.nonzero(row_mass)[0]
        return SparseTensor(indices, t[indices], t.shape)

    def to_dense(self):
        out = jnp.zeros(self.dense_size, self.values.dtype)
        return out.at[self.indices].add(self.values)

    def sparse_size(self) -> int:
        return int(self.indices.size + self.values.size)


def _scatter_rows(tokens, g_rows, vocab: int, dtype):
    """Σ over token occurrences: dense [vocab, H] from compact pairs."""
    H = g_rows.shape[-1]
    flat_tok = tokens.reshape(-1)
    flat_g = g_rows.reshape(-1, H).astype(jnp.float32)
    out = jnp.zeros((vocab, H), jnp.float32)
    return out.at[flat_tok].add(flat_g).astype(dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def sparse_embedding_lookup(table, tokens, data_axes: Optional[Tuple[str, ...]] = None):
    """``table[tokens]`` whose backward reduces over DP as compact pairs.

    ``data_axes``: mesh axis names the batch's leading dim is sharded over
    (``Topology.dense_batch_axes()``); None/empty → single-shard scatter-add
    (no collective at all).
    """
    return table[tokens]


def _sel_fwd(table, tokens, data_axes):
    # the table itself rides the residuals only for its STATIC aval
    # (shape/dtype); its data is unused in bwd and DCE'd by XLA
    return table[tokens], (table, tokens)


def _sel_bwd(data_axes, res, g):
    table, tokens = res
    (vocab, hidden), dtype = table.shape, table.dtype
    axes: Tuple[str, ...] = tuple(data_axes) if data_axes else ()
    if axes:
        from deepspeed_tpu.parallel.mesh import get_topology

        topo = get_topology()
        axes = tuple(a for a in axes if topo.axis_size(a) > 1)
    if not axes:
        return _scatter_rows(tokens, g, vocab, dtype), None

    mesh = topo.mesh

    def inner(tok_shard, g_shard):
        # the compact pairs are what crosses the interconnect
        toks_all = jax.lax.all_gather(tok_shard, axes, axis=0, tiled=True)
        g_all = jax.lax.all_gather(g_shard, axes, axis=0, tiled=True)
        return _scatter_rows(toks_all, g_all, vocab, dtype)

    batch_spec = axes if len(axes) > 1 else axes[0]
    d_table = shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(batch_spec, None), P(batch_spec, None, None)),
        out_specs=P(),
        check_vma=False,
    )(tokens, g)
    return d_table, None


sparse_embedding_lookup.defvjp(_sel_fwd, _sel_bwd)
