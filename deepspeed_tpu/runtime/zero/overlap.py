"""Comm/compute overlap for ZeRO training: the software-pipeline plan.

The reference hides ZeRO communication behind compute with a prefetch
coordinator (``partitioned_param_coordinator.py`` driven by
``stage3_prefetch_bucket_size`` / ``overlap_comm``) and reduces gradients in
buckets while backward is still running (``stage_1_and_2.py:961``
``average_tensor``). Our GSPMD port declared those knobs but left the
schedule to XLA — which gathers each scanned layer's shards at its use
point and reduces the stacked gradient in one monolithic tail collective.

This module is the mechanism. An :class:`OverlapPlan` is built by the
engine from the ZeRO config + the stacked ``params["layers"]`` sharding
trees and activated (trace-time, via :func:`overlap_scope`) around the
training loss; the model's scanned layer stack then restructures into a
software pipeline:

* **Pipelined parameter gather** (stage 3) — the scan body computes layer
  *i* from a double-buffered carry of already-gathered params while
  issuing the all-gather for layer *i+depth* (``zero.prefetch_layers``,
  capped so in-flight gathered elements honor
  ``stage3_prefetch_bucket_size``). The gather is a
  ``with_sharding_constraint`` from the ZeRO-sharded per-layer spec to the
  spec with the ZeRO axes stripped — exact, so the pipelined step is
  bit-identical to the unpipelined one. The stacked tree is SCANNED
  (``xs``): each iteration is handed its own ZeRO-cut slice, the layer's
  cotangent is transposed onto that slice (:meth:`OverlapPlan.use_buffered`)
  and so leaves the backward scan as its output (``ys``), one layer
  written in place an iteration. Only the lookahead indexes a stack the
  body closes over, and that one is a ``stop_gradient`` view: a
  closed-over stack WITH a gradient gets a whole-stack accumulator in the
  backward carry (``acc += update_slice(zeros, g, i)``, a pass over all L
  layers to add one — 16 ``select_add`` fusions a layer in GPT-2 XL's
  step, PR 55), and even an instantiated zero sent to the lookahead's index
  would keep that accumulator alive.
* **Bucketed gradient reduce-scatter** (stage >= 2) — an identity
  ``custom_vjp`` around the per-layer params whose backward pins each
  layer's cotangent to its scattered layout *inside* the backward scan,
  coalescing leaves into ``reduce_bucket_size``-element buckets through
  the ``[world, chunk]`` row layout of
  ``runtime/comm/coalesced_collectives.py`` — one reduce-scatter per
  bucket per layer, issued as backward produces it, instead of one tail
  barrier over the whole stacked gradient. The packing is pure data
  movement (transpose + pad + concat), so values are unchanged.

Both transforms are value-preserving by construction; the parity suite
(tests/unit/runtime/zero/test_overlap.py) enforces bit-identity against
the unpipelined step, and the ``overlap`` analysis pass verifies the
compiled schedule actually has compute to hide each loop collective
behind.
"""

from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.runtime.comm.coalesced_collectives import (
    pack_row_coalesced,
    unpack_row_coalesced,
)

_is_spec = lambda x: isinstance(x, P)  # noqa: E731


def _entry_axes(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(a for a in entry if a is not None)
    return (entry,)


def _strip_axes(entry, drop: set):
    kept = tuple(a for a in _entry_axes(entry) if a not in drop)
    if not kept:
        return None
    if len(kept) == 1:
        return kept[0]
    return kept


@dataclass
class _LeafInfo:
    """Static per-leaf metadata for one unstacked ``params['layers']`` leaf."""

    shape: Tuple[int, ...]  # per-layer (unstacked) shape
    gather_spec: P  # per-layer spec with ZeRO axes stripped (the gather target)
    grad_spec: P  # per-layer grad spec (the scattered reduce target)
    scatter_dim: int  # dim of grad_spec carrying the ZeRO axes; -1 if none
    coalescable: bool  # ZeRO axes are the ONLY sharding → row-layout packable


@dataclass
class OverlapPlan:
    """Trace-time comm-overlap schedule for one engine's scanned layer stack."""

    mesh: Any
    zero_axes: Tuple[str, ...]
    zero_world: int
    depth: int  # layers gathered AHEAD of use; 0 = explicit use-point gather
    prefetch_enabled: bool
    reduce_enabled: bool
    reduce_bucket_elems: int
    leaves: List[_LeafInfo] = field(default_factory=list)
    treedef: Any = None
    # --- a2a stage (expert-parallel MoE dispatch/combine) --------------
    # The MoE layer family reads these through active_plan() while tracing:
    # a2a_axis names the mesh axis the dispatch/combine all-to-alls run
    # over, and a2a_quantized selects the int8 wire format of
    # moe/a2a.py:quantized_all_to_all (None defers to the layer's own
    # knob). The a2as themselves are emitted by the layer — dispatch
    # before the shared-expert/dense branch so XLA schedules it behind
    # that independent compute, combine before the next layer's gating —
    # and the overlap analysis pass verifies the schedule has real
    # compute to hide each one behind.
    a2a_axis: Optional[str] = None
    a2a_world: int = 1
    a2a_quantized: Optional[bool] = None

    @property
    def a2a_enabled(self) -> bool:
        return self.a2a_axis is not None and self.a2a_world > 1

    # --- pipelined parameter gather ------------------------------------
    def pin_gathered(self, per_layer: Any) -> Any:
        """Re-pin an already-gathered per-layer tree to the gathered
        sharding. Applied where the carried double buffer is CONSUMED: the
        partitioner unifies a while carry's sharding across init, body root
        and body uses, and the autodiff-saved carry stack pulls it toward
        the sharded layout — without this use-point anchor the carry gets
        resharded and the use re-gathers, silently undoing the pipeline."""
        flat, treedef = jax.tree_util.tree_flatten(per_layer)
        out = [
            jax.lax.with_sharding_constraint(
                t, NamedSharding(self.mesh, info.gather_spec)
            )
            for t, info in zip(flat, self.leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def gather_layer(self, stacked: Any, i) -> Any:
        """Slice layer ``i`` from a stacked [L, ...] tree and constrain it
        to the gathered (ZeRO-axes-stripped) sharding — the all-gather the
        pipeline issues AHEAD of use, for the prologue (``i`` a python int)
        and the lookahead (a traced scan index). ``stacked`` must carry no
        gradient (the scan hands it a ``stop_gradient`` view): the transpose
        of an index into a stack the scan closes over is a whole-stack
        accumulator in the backward carry, ``acc += update_slice(zeros, g,
        i)`` once a layer — a pass over every [L, ...] leaf to add one
        layer's gradient. The layer's gradient leaves through
        :meth:`use_buffered` instead."""
        return self.pin_gathered(
            jax.tree_util.tree_map(
                lambda leaf: jax.lax.dynamic_index_in_dim(
                    leaf, i, axis=0, keepdims=False
                ),
                stacked,
            )
        )

    def use_buffered(self, mine: Any, buf: Any) -> Any:
        """Consume a prefetched per-layer buffer with USE-POINT autodiff.

        ``mine`` is this iteration's own ZeRO-cut slice of the stack, handed
        in by the scan as ``xs``; ``buf`` the double-buffered carry value
        (the gather issued ``depth`` layers ago — the schedule the pipeline
        exists for). Forward: ``buf``. Backward: ``jax.linear_transpose`` of
        the gather (:meth:`pin_gathered`, the constraint alone) onto
        ``mine`` — the exact transpose the depth-0 use-point gather gets
        from autodiff — and nothing to ``buf``. So the layer's cotangent
        leaves the backward scan as that iteration's ``ys`` slice, written
        once in place, at every depth. Without this, the buffer's cotangent
        travels back through ``depth`` backward-scan carries and the
        partitioner re-derives the cross-device grad reduction around the
        carry's layout — measured on the 8-device mesh as last-ulp grad
        drift vs depth 0 (all-reduce vs reduce-scatter summation order).
        Routing the cotangent through the same ops as depth 0 makes depth-k
        bit-identical BY CONSTRUCTION. Sound because the pipeline invariant
        holds bit-wise: buf IS pin_gathered(mine) — both pure data movement
        of the same shards."""
        avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), mine
        )

        @jax.custom_vjp
        def _use(mine, buf):
            return buf

        def _fwd(mine, buf):
            return buf, None

        def _bwd(_, g):
            (d_mine,) = jax.linear_transpose(self.pin_gathered, avals)(g)
            return (d_mine, None)

        _use.defvjp(_fwd, _bwd)
        return _use(mine, buf)

    # --- bucketed in-scan gradient reduction ---------------------------
    def reduce_grads(self, per_layer: Any) -> Any:
        """Identity on the per-layer param tree whose backward issues this
        layer's gradient reduction right where the layer's backward runs —
        inside the scan, coalesced into ``reduce_bucket_size``-element
        buckets — instead of one monolithic tail barrier.

        The in-loop constraint materializes the cross-batch sum in the
        gathered-over-ZeRO layout (ONE collective per bucket; without it
        XLA emits one per leaf, or defers the whole reduction to the tail).
        The SCATTERED stage-2/3 layout then lands at the engine's grad
        shardings — a free local slice once the sum exists. In the
        pipelined scan the reduced cotangent leaves the loop as the
        backward scan's ``ys``, and the partitioner gives that stack the
        engine's cut layout: GPT-2 XL's step compiled for a described
        ``v5e:2x2`` (PR 55) all-reduces the bucket in the loop body,
        slices, and writes each chip's quarter of a layer in place
        (``dynamic-update-slice`` into ``[48,400,1600]``; ``w_in`` and
        ``w_out`` cut are ``[48,1600,1600]``). It is all-reduce + slice,
        not a reduce-scatter: pinning the scattered layout here is
        untried since the stack left the carry."""
        if not self.reduce_enabled:
            return per_layer

        @jax.custom_vjp
        def _reduce_boundary(tree):
            return tree

        def _fwd(tree):
            return tree, None

        def _bwd(_, g):
            with jax.named_scope("grad_reduce"):
                return (self._coalesce_cotangent(g),)

        _reduce_boundary.defvjp(_fwd, _bwd)
        return _reduce_boundary(per_layer)

    def _coalesce_cotangent(self, g: Any) -> Any:
        """Coalesce one layer's cotangent tree into element-capped buckets
        via the shared ``[world, chunk]`` row layout and force each
        bucket's reduction with a single gathered-layout constraint. Pure
        data movement around one collective per bucket — values untouched.
        Leaves with TP-mixed sharding stay un-coalesced (their layout is
        not row-packable with the pure-ZeRO leaves)."""
        flat, treedef = jax.tree_util.tree_flatten(g)

        # group coalescable leaves by dtype (a packed buffer is one dtype),
        # then split each group into element-capped buckets, preserving
        # tree order so the bucket layout is deterministic across traces
        groups: dict = {}
        for idx, (leaf, info) in enumerate(zip(flat, self.leaves)):
            if leaf is None:  # symbolic zero cotangent: nothing to reduce
                continue
            if info.coalescable:
                groups.setdefault(str(leaf.dtype), []).append(idx)

        out = list(flat)
        for idxs in groups.values():
            for bucket in _split_buckets(
                idxs, [self.leaves[i] for i in idxs], self.reduce_bucket_elems
            ):
                infos = [self.leaves[i] for i in bucket]
                if len(bucket) == 1:
                    i, info = bucket[0], infos[0]
                    out[i] = jax.lax.with_sharding_constraint(
                        flat[i], NamedSharding(self.mesh, info.gather_spec)
                    )
                    continue
                moved = [
                    jnp.moveaxis(flat[i], info.scatter_dim, 0)
                    for i, info in zip(bucket, infos)
                ]
                buf = pack_row_coalesced(moved, self.zero_world)
                # ONE reduction for the whole bucket (coalescable leaves are
                # pure-ZeRO sharded, so gathered-over-ZeRO == replicated)
                buf = jax.lax.with_sharding_constraint(
                    buf, NamedSharding(self.mesh, P(None, None))
                )
                parts = unpack_row_coalesced(
                    buf, [m.shape for m in moved], self.zero_world
                )
                for i, info, part in zip(bucket, infos, parts):
                    out[i] = jnp.moveaxis(part, 0, info.scatter_dim)
        return jax.tree_util.tree_unflatten(treedef, out)


def _entry_axes_nonempty(spec: P) -> bool:
    return any(_entry_axes(e) for e in spec)


def _split_buckets(
    idxs: List[int], infos: List[_LeafInfo], cap_elems: int
) -> List[List[int]]:
    """Greedy size-targeted grouping (reference ``reduce_bucket_size``
    semantics: element count per collective). Every bucket holds >= 1 leaf;
    an oversized single leaf rides alone."""
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_elems = 0
    cap = max(int(cap_elems), 1)
    for i, info in zip(idxs, infos):
        n = int(np.prod(info.shape)) if info.shape else 1
        if cur and cur_elems + n > cap:
            buckets.append(cur)
            cur, cur_elems = [], 0
        cur.append(i)
        cur_elems += n
    if cur:
        buckets.append(cur)
    return buckets


def build_overlap_plan(
    zero_config,
    topo,
    stacked_tree: Any,
    stacked_param_specs: Any,
    stacked_grad_specs: Any,
    num_layers: int,
    moe_quantized_a2a: Optional[bool] = None,
) -> Optional[OverlapPlan]:
    """Build the plan from the ZeRO config + the STACKED ``params['layers']``
    trees (arrays-or-shaped leaves + param/grad PartitionSpecs, leading dim
    = L). Returns None when no stage is enabled: neither ZeRO transform
    (stage < 2, or overlap off with no explicit ``prefetch_layers``) nor
    the expert-parallel a2a stage (mesh has no real ``expert`` axis).

    ``prefetch_layers`` semantics: ``None`` → one layer of lookahead when
    stage-3 overlap is on (the reference's default prefetch), nothing
    otherwise; ``k >= 1`` → a k-deep software pipeline; ``0`` → the
    EXPLICIT use-point gather — the same gather/constraint structure as the
    pipeline but issued at the layer's own iteration, zero lookahead. Depth
    0 is the "unpipelined step" of the parity contract: depth only moves
    where the gather is issued, never what is computed, so depth-k and
    depth-0 programs are bit-identical (the parity suite enforces =, not
    allclose). The raw scan (no plan) lets GSPMD place the gather itself,
    which re-partitions the backward and reassociates the distributed grad
    sum at the last ulp — so raw-vs-explicit is compared at tight rtol
    instead."""
    stage = int(zero_config.stage)
    overlap = bool(zero_config.overlap_comm)
    prefetch_layers = getattr(zero_config, "prefetch_layers", None)
    if prefetch_layers is None and stage >= 3 and overlap:
        prefetch_layers = 1
    prefetch = stage >= 3 and prefetch_layers is not None
    reduce_ = stage >= 2 and overlap and bool(zero_config.reduce_scatter)
    # a2a stage: armed whenever the mesh has a real expert axis — the MoE
    # layer family routes its dispatch/combine exchange through it
    a2a_world = int(topo.axis_size("expert")) if "expert" in topo.mesh.axis_names else 1
    a2a = a2a_world > 1
    if not prefetch and not reduce_ and not a2a:
        return None

    zero_axes = tuple(topo.zero_shard_axes)
    zero_world = int(np.prod([topo.axis_size(a) for a in zero_axes])) if zero_axes else 1
    if zero_world <= 1:
        prefetch = reduce_ = False
        if not a2a:
            return None
    drop = set(zero_axes)
    # size-1 mesh axes don't partition anything: ignore them when deciding
    # what a leaf's "real" sharding is (TP rules emit 'model' entries even
    # on a pure-data mesh), but keep them in the emitted specs
    trivial = {a for a in topo.mesh.axis_names if topo.axis_size(a) == 1}

    arr_flat, treedef = jax.tree_util.tree_flatten(stacked_tree)
    pspecs_flat = treedef.flatten_up_to(stacked_param_specs)
    gspecs_flat = treedef.flatten_up_to(stacked_grad_specs)

    leaves: List[_LeafInfo] = []
    gathered_elems = 0
    for arr, pspec, gspec in zip(arr_flat, pspecs_flat, gspecs_flat):
        shape = tuple(int(d) for d in arr.shape)
        per_shape = shape[1:]
        p_entries = list(pspec) + [None] * (len(shape) - len(list(pspec)))
        g_entries = list(gspec) + [None] * (len(shape) - len(list(gspec)))
        # per-layer view: drop the scanned L dim (entry 0)
        gather_spec = P(*[_strip_axes(e, drop) for e in p_entries[1:]])
        grad_spec = P(*g_entries[1:])
        scatter_dim = -1
        coalescable = False
        for d, e in enumerate(g_entries[1:]):
            axes = _entry_axes(e)
            if set(axes) & drop:
                scatter_dim = d
                # packable iff the ZeRO axes are this leaf's ONLY effective
                # sharding — a TP-stacked dim or a second sharded dim would
                # need its own buffer layout, so it reduces un-coalesced
                others = [
                    a
                    for ee in g_entries[1:]
                    for a in _entry_axes(ee)
                    if a not in drop and a not in trivial
                ]
                effective = tuple(a for a in axes if a not in trivial)
                coalescable = effective == tuple(zero_axes) and not others
                break
        # a leaf whose ZeRO sharding landed on the scanned L dim itself
        # yields an already-replicated per-layer slice — nothing to gather
        if not (set(_entry_axes(p_entries[0])) & drop) and any(
            set(_entry_axes(e)) & drop for e in p_entries[1:]
        ):
            gathered_elems += int(np.prod(per_shape)) if per_shape else 1
        leaves.append(
            _LeafInfo(
                shape=per_shape,
                gather_spec=gather_spec,
                grad_spec=grad_spec,
                scatter_dim=scatter_dim,
                coalescable=coalescable,
            )
        )

    depth = 0
    if prefetch:
        depth = min(int(prefetch_layers), int(num_layers))
        budget = int(zero_config.prefetch_bucket_size)
        if budget > 0 and gathered_elems > 0:
            # cap in-flight prefetched elements (depth layers beyond the one
            # in use) at stage3_prefetch_bucket_size, never below 1 layer
            while depth > 1 and depth * gathered_elems > budget:
                depth -= 1
        if gathered_elems == 0:
            prefetch = False  # nothing is ZeRO-sharded (all persistent)
            depth = 0
    if not prefetch and not reduce_ and not a2a:
        return None

    return OverlapPlan(
        mesh=topo.mesh,
        zero_axes=zero_axes,
        zero_world=zero_world,
        depth=depth,
        prefetch_enabled=prefetch,
        reduce_enabled=reduce_,
        reduce_bucket_elems=int(zero_config.reduce_bucket_size) or 1,
        leaves=leaves,
        treedef=treedef,
        a2a_axis="expert" if a2a else None,
        a2a_world=a2a_world,
        a2a_quantized=moe_quantized_a2a,
    )


# --- trace-time activation --------------------------------------------------
_ACTIVE: List[OverlapPlan] = []


@contextmanager
def overlap_scope(plan: Optional[OverlapPlan]):
    """Activate ``plan`` for the duration of a trace. The engine wraps its
    training-loss closures with this; the model family reads
    :func:`active_plan` while tracing its layer stack."""
    if plan is None:
        yield
        return
    _ACTIVE.append(plan)
    try:
        yield
    finally:
        _ACTIVE.pop()


def active_plan() -> Optional[OverlapPlan]:
    return _ACTIVE[-1] if _ACTIVE else None
