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
* **In-loop gradient reduction** (stage >= 2) — an identity
  ``custom_vjp`` around the per-layer params whose backward forces each
  layer's cross-batch sum *inside* the backward scan, as backward produces
  it, instead of one tail barrier over the whole stacked gradient. Every
  leaf whose only sharding is ZeRO's is reduced ALONE, where it lies: one
  sharding constraint on the leaf in its own shape. The TPU compiler turns
  a matrix's into its fused reduce-scatter with the slice to the engine's
  cut layout behind it, and combines the small leaves' all-reduces (the
  norms' and biases' vectors, all latency) into one by itself, so the plan
  builds no bucket: packing a layer's leaves into one ``[world, chunk]``
  buffer saved no collective (the compiler reduced the pack's pieces, not
  the pack) and cost a relayout of every element (173 ms of GPT-2 XL's
  1,078 ms step until PR 58). :meth:`OverlapPlan.reduction_record` says
  which leaves the plan reduces.

Both transforms are value-preserving by construction; the parity suite
(tests/unit/runtime/zero/test_overlap.py) enforces bit-identity against
the unpipelined step, and the ``overlap`` analysis pass verifies the
compiled schedule actually has compute to hide each loop collective
behind.
"""

from __future__ import annotations

import math
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

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
    # the ZeRO axes are this leaf's ONLY sharding: gathered over ZeRO it is
    # replicated, so the constraint to gather_spec is its whole reduction
    zero_only: bool


@dataclass
class OverlapPlan:
    """Trace-time comm-overlap schedule for one engine's scanned layer stack."""

    mesh: Any
    zero_axes: Tuple[str, ...]
    depth: int  # layers gathered AHEAD of use; 0 = explicit use-point gather
    prefetch_enabled: bool
    reduce_enabled: bool
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

    # --- in-scan gradient reduction ------------------------------------
    def reduce_grads(self, per_layer: Any) -> Any:
        """Identity on the per-layer param tree whose backward issues this
        layer's gradient reduction right where the layer's backward runs,
        inside the scan, instead of one monolithic tail barrier.

        The in-loop constraint materializes the cross-batch sum in the
        gathered-over-ZeRO layout; without it XLA defers the whole reduction
        to the tail. The SCATTERED stage-2/3 layout then lands at the
        engine's grad shardings: in the pipelined scan the reduced cotangent
        leaves the loop as the backward scan's ``ys``, and the partitioner
        gives that stack the engine's cut layout, so what follows the sum
        is a slice to a chip's quarter. The TPU compiler fuses the two:
        GPT-2 XL's step compiled for a described ``v5e:2x2`` (PR 58) holds,
        in the backward loop body, five matrices as its fused
        reduce-scatter (``%all-reduce-scatter.*`` on the whole
        ``[6400,1600]`` / ``[1600,1600]`` operand, padded by a tile row: a
        chip gets ``[1632,1600]`` / ``[416,1600]`` and a halo exchange, a
        ``collective-permute`` of 96 / 48 rows, turns that into the
        engine's 1,600 / 400), and ``w_in`` ``[1600,6400]``, cut by
        columns, in the one all-reduce the compiler combines eight of the
        ten vectors' into (two biases share another), sliced after it.
        Three other forms were measured on the chip by the refused PR 57's
        builder and dropped (PERF.md section 6, PR 58). The leaf's scattered
        grad spec in the constraint: the same compiled text (the gather's
        transpose in :meth:`use_buffered` re-imposes the gathered layout).
        ``w_in`` in a rows-leading view, a sixth reduce-scatter: its
        weight-gradient matmul loses more than the collective saves (883.3
        against 880.4 ms a step). The ten vectors in one ``[world, chunk]``
        bucket: the same two all-reduces and thirteen more reshapes (9,305.3
        against 9,308.3 tokens/s/chip with every leaf alone)."""
        if not self.reduce_enabled:
            return per_layer

        @jax.custom_vjp
        def _reduce_boundary(tree):
            return tree

        def _fwd(tree):
            return tree, None

        def _bwd(_, g):
            with jax.named_scope("grad_reduce"):
                return (self._reduce_cotangent(g),)

        _reduce_boundary.defvjp(_fwd, _bwd)
        return _reduce_boundary(per_layer)

    def _reduce_cotangent(self, g: Any) -> Any:
        """Force the reduction of one layer's cotangent tree: one
        gathered-layout constraint on every ``zero_only`` leaf, in its own
        shape. A leaf with TP-mixed sharding is left to the partitioner."""
        flat, treedef = jax.tree_util.tree_flatten(g)
        out = [
            jax.lax.with_sharding_constraint(t, NamedSharding(self.mesh, info.gather_spec))
            if info.zero_only
            else t
            for t, info in zip(flat, self.leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def reduction_record(self) -> Dict[str, Any]:
        """Which of a layer's leaves :meth:`reduce_grads` reduces in the
        loop, each alone, and which it leaves to the partitioner: the engine
        emits it once, where it builds the plan (GPT-2 XL's: 16 alone)."""
        alone = [math.prod(info.shape) for info in self.leaves if info.zero_only]
        return {
            "leaves_alone": len(alone),
            "leaves_left_to_partitioner": len(self.leaves) - len(alone),
            "alone_elems": alone,
        }


def _entry_axes_nonempty(spec: P) -> bool:
    return any(_entry_axes(e) for e in spec)


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
        zero_only = False
        for e in g_entries[1:]:
            axes = _entry_axes(e)
            if set(axes) & drop:
                # the ZeRO axes are this leaf's ONLY effective sharding: a
                # TP-stacked dim or a second sharded dim stays sharded in the
                # gathered layout, and that leaf is left to the partitioner
                others = [
                    a
                    for ee in g_entries[1:]
                    for a in _entry_axes(ee)
                    if a not in drop and a not in trivial
                ]
                effective = tuple(a for a in axes if a not in trivial)
                zero_only = effective == tuple(zero_axes) and not others
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
                zero_only=zero_only,
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
        depth=depth,
        prefetch_enabled=prefetch,
        reduce_enabled=reduce_,
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
