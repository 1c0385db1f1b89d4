"""SPMD pipeline executor: the pipe-axis>1 path.

TPU-native replacement for the reference's instruction-interpreting
``PipelineEngine`` (``deepspeed/runtime/pipe/engine.py:54``) and its p2p layer
(``deepspeed/runtime/pipe/p2p.py``): instead of per-process send/recv with a
tensor-meta handshake, the whole pipeline is ONE jitted XLA program —
``shard_map`` manual over the ``pipe`` mesh axis, stage handoffs are
``ppermute`` collectives riding ICI, and the microbatch interleave is a
``lax.scan`` over pipeline ticks. Autodiff through the scan generates the
backward schedule (SendGrad/RecvGrad become the transposed ppermutes), so
forward and backward stay in lockstep with ``schedule.TrainSchedule``'s
ordering without an interpreter.

Structure of one forward (M microbatches, S stages, T = M + S - 1 ticks):

    prefix (embedding &c.)  — computed once on the full batch, replicated
                              over the pipe axis (cheap gather-type work; the
                              same choice GSPMD pipelining makes)
    tick t in [0, T):         stage 0 ingests microbatch t (while t < M);
                              every stage applies its K local layers;
                              outputs ppermute to the next stage
    suffix (head + loss)    — computed on the full collected output,
                              replicated over pipe

Memory: ``lax.scan`` retains each tick's carry (one microbatch activation)
plus per-stage remat'd layer state — the activation footprint of GPipe with
recomputation; the 1F1B live-buffer bound is recovered because XLA schedules
the backward ticks interleaved with forward recomputation.

The stage body requires the pipelined run of layers to be *homogeneous*
(identical param structure and activation shape) — true of the transformer
stacks pipeline parallelism is used for. Heterogeneous prologue/epilogue
layers (embeddings, norms, heads) are detected automatically and run as
prefix/suffix.
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.runtime.module import DSModule
from deepspeed_tpu.utils.logging import log_dist


def _tree_shapes(tree) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return treedef, tuple((tuple(l.shape), jnp.dtype(l.dtype).name) for l in leaves)


def _shape_of(tree):
    return jax.tree_util.tree_map(lambda l: (tuple(l.shape), jnp.dtype(l.dtype).name), tree)


class PipelineLayout:
    """Prefix / homogeneous-body / suffix split of a layer sequence."""

    def __init__(self, b0: int, b1: int, num_layers: int):
        self.b0 = b0
        self.b1 = b1
        self.num_layers = num_layers

    @property
    def body_len(self) -> int:
        return self.b1 - self.b0


def detect_layout(layers: List[Any], sample_x, rng) -> PipelineLayout:
    """Find the maximal contiguous run of layers with identical parameter
    structure and identical (shape-preserving) activation signature — the
    pipelinable body. Uses abstract evaluation only."""
    sigs = []  # (param_sig, in_sig, out_sig) per layer
    x = sample_x
    for layer in layers:
        p_shape = jax.eval_shape(lambda r, xx, l=layer: l.init(r, xx), rng, x)
        out = jax.eval_shape(lambda pp, xx, l=layer: l.apply(pp, xx, train=True), p_shape, x)
        sigs.append((_tree_shapes(p_shape), _shape_of(x), _shape_of(out)))
        x = out
    best = (0, 0)
    i = 0
    n = len(layers)
    while i < n:
        j = i
        while (
            j < n
            and sigs[j][0] == sigs[i][0]
            and sigs[j][1] == sigs[i][1]
            and sigs[j][2] == sigs[i][1]  # shape-preserving
        ):
            j += 1
        if j - i > best[1] - best[0]:
            best = (i, j)
        i = max(j, i + 1)
    return PipelineLayout(best[0], best[1], n)


class SpmdPipelineModule(DSModule):
    """Wraps a ``PipelineModule`` for execution over a pipe mesh axis > 1.

    Parameters are re-laid-out as::

        {"prefix": [tree, ...],          # replicated over pipe
         "body":   tree with leading [L_body] dim, sharded over pipe,
         "suffix": [tree, ...]}          # replicated over pipe

    and ``apply`` runs the collective-loop pipeline documented in the module
    docstring. ``num_micro`` microbatches are cut from the incoming batch's
    leading dim (so callers pass the full gradient-accumulation batch at
    once — the reference's ``PipelineEngine.train_batch`` contract,
    pipe/engine.py:297).
    """

    def __init__(self, pipeline_module, topology, num_micro: int):
        self.inner = pipeline_module
        self.topology = topology
        self.num_stages = topology.get_pipe_parallel_world_size()
        self.num_micro = max(num_micro, 1)
        self.loss_fn = pipeline_module.loss_fn
        self._layout: Optional[PipelineLayout] = None
        self._layers = None

    # --- layout -----------------------------------------------------------
    def _sample_x(self, batch):
        x = batch
        if isinstance(batch, (tuple, list)) and len(batch) == 2:
            x = batch[0]
        elif isinstance(batch, dict):
            x = batch.get("input_ids", batch)
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(np.shape(l), _np_dtype(l)), x
        )

    def _ensure_layout(self, batch):
        if self._layout is not None:
            return
        self._layers = self.inner.build_layers()
        rng = jax.random.PRNGKey(0)
        layout = detect_layout(self._layers, self._sample_x(batch), rng)
        S = self.num_stages
        if layout.body_len < S:
            raise ValueError(
                f"pipeline body of {layout.body_len} homogeneous layers cannot fill "
                f"{S} stages; reduce the pipe axis or add layers"
            )
        if layout.body_len % S != 0:
            # shrink the run from the tail so stages stay balanced
            layout.b1 -= layout.body_len % S
        self._layout = layout
        log_dist(
            f"SpmdPipelineModule: {layout.num_layers} layers → prefix[:{layout.b0}] "
            f"+ body[{layout.b0}:{layout.b1}] over {S} stages "
            f"({layout.body_len // S}/stage) + suffix[{layout.b1}:], "
            f"{self.num_micro} microbatches",
            ranks=[0],
        )

    # --- DSModule surface -------------------------------------------------
    def init(self, rng, batch):
        self._ensure_layout(batch)
        lo = self._layout
        layers = self._layers
        x = self._sample_x(batch)

        prefix_params, body_params, suffix_params = [], [], []
        for i, layer in enumerate(layers):
            rng, sub = jax.random.split(rng)
            p = layer.init(sub, _materialize(x))
            if i < lo.b0:
                prefix_params.append(p)
            elif i < lo.b1:
                body_params.append(p)
            else:
                suffix_params.append(p)
            out = jax.eval_shape(lambda pp, xx, l=layer: l.apply(pp, xx, train=True), p, x)
            x = out
        stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls, axis=0), *body_params)
        return {"prefix": prefix_params, "body": stacked, "suffix": suffix_params}

    def tp_partition_rules(self, params_shapes=None):
        if params_shapes is None:
            return None

        def body_spec(leaf):
            return P("pipe", *([None] * (len(leaf.shape) - 1)))

        def rep(leaf):
            return P(*([None] * len(leaf.shape)))

        return {
            "prefix": jax.tree_util.tree_map(rep, params_shapes["prefix"]),
            "body": jax.tree_util.tree_map(body_spec, params_shapes["body"]),
            "suffix": jax.tree_util.tree_map(rep, params_shapes["suffix"]),
        }

    def apply(self, params, batch, *, rngs=None, train: bool = True):
        self._ensure_layout(batch)
        lo = self._layout
        layers = self._layers
        S = self.num_stages
        M = self.num_micro
        K = lo.body_len // S
        mesh = self.topology.mesh

        if isinstance(batch, (tuple, list)) and len(batch) == 2:
            x, labels = batch
        elif isinstance(batch, dict):
            x, labels = batch.get("input_ids", batch), batch.get("labels")
        else:
            x, labels = batch, None

        # prefix on the full batch (replicated over pipe; per-sample ops so
        # full-batch == per-microbatch evaluation)
        for i in range(lo.b0):
            x = layers[i].apply(params["prefix"][i], x, train=train)

        # anchor the batch dim to the data axes on BOTH sides of the pipe
        # region: without an explicit constraint XLA's propagation picks a
        # different layout for the prefix output than the pipeline body wants
        # and falls back to a full replicate-then-reshard of every microbatch
        # handoff ("[SPMD] Involuntary full rematerialization")
        batch_axes = self.topology.dense_batch_axes()
        from jax.sharding import NamedSharding

        def pin_batch(tree, batch_dim=0):
            if batch_axes is None:
                return tree

            def leaf(l):
                entries = [None] * l.ndim
                entries[batch_dim] = batch_axes
                return jax.lax.with_sharding_constraint(
                    l, NamedSharding(mesh, P(*entries))
                )

            return jax.tree_util.tree_map(leaf, tree)

        x = pin_batch(x)
        B = jax.tree_util.tree_leaves(x)[0].shape[0]
        if B % M != 0:
            raise ValueError(f"batch dim {B} not divisible by {M} microbatches")
        b = B // M
        mbs = jax.tree_util.tree_map(lambda l: l.reshape((M, b) + l.shape[1:]), x)
        mbs = pin_batch(mbs, batch_dim=1)

        # XLA-CPU's AllReducePromotion pass crashes on sub-f32 collectives
        # generated by this region's transposes (cotangent psum / the emits
        # reduce-scatter); promote boundary tensors to f32 on CPU only.
        promote = jax.default_backend() == "cpu"
        act_dtypes = jax.tree_util.tree_map(lambda l: l.dtype, mbs)
        if promote:
            mbs = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), mbs)

        body_layer = layers[lo.b0]  # homogeneous: one representative

        def stage_fn(stage_params, h):
            """Apply this stage's K layers (scanned over the local stack)."""

            def one_layer(carry, per_layer):
                return body_layer.apply(per_layer, carry, train=train), None

            one_layer = jax.checkpoint(one_layer, prevent_cse=False)
            out, _ = jax.lax.scan(one_layer, h, stage_params)
            return out

        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        T = M + S - 1

        def pipeline_body(body_params_local, mbs_in):
            s = jax.lax.axis_index("pipe")

            def tick(carry, t):
                state = carry
                ingest = jax.tree_util.tree_map(
                    lambda m: m[jnp.minimum(t, M - 1)], mbs_in
                )
                inp = jax.tree_util.tree_map(
                    lambda a, c: jnp.where(s == 0, a, c), ingest, state
                )
                if promote:
                    inp = jax.tree_util.tree_map(lambda l, d: l.astype(d), inp, act_dtypes)
                out = stage_fn(body_params_local, inp)
                if promote:
                    out = jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), out)
                nxt = jax.tree_util.tree_map(
                    lambda o: jax.lax.ppermute(o, "pipe", fwd_perm), out
                )
                return nxt, out

            zero_state = jax.tree_util.tree_map(lambda m: jnp.zeros_like(m[0]), mbs_in)
            _, emits = jax.lax.scan(tick, zero_state, jnp.arange(T))
            # ticks [S-1, T) carry the last stage's outputs for microbatches
            # [0, M); all_gather + index broadcasts them off the last stage
            # (bf16-safe, unlike a masked psum which trips XLA-CPU's
            # AllReducePromotion pass)
            outs = jax.tree_util.tree_map(
                lambda e: jax.lax.all_gather(e[S - 1 :], "pipe", axis=0)[S - 1], emits
            )
            return outs

        pipelined = shard_map(
            pipeline_body,
            mesh=mesh,
            in_specs=(
                jax.tree_util.tree_map(lambda _: P("pipe"), params["body"]),
                jax.tree_util.tree_map(lambda _: P(), mbs),
            ),
            out_specs=jax.tree_util.tree_map(lambda _: P(), mbs),
            axis_names={"pipe"},
            check_vma=False,
        )
        outs = pipelined(params["body"], mbs)
        if promote:
            outs = jax.tree_util.tree_map(lambda o, d: o.astype(d), outs, act_dtypes)
        outs = pin_batch(outs, batch_dim=1)
        x = jax.tree_util.tree_map(lambda o: o.reshape((B,) + o.shape[2:]), outs)
        x = pin_batch(x)

        # suffix + loss on the full collected output (replicated over pipe)
        for i in range(lo.b1, lo.num_layers):
            x = layers[i].apply(params["suffix"][i - lo.b1], x, train=train)
        if self.loss_fn is not None and labels is not None:
            return self.loss_fn(x, labels)
        return x


def _np_dtype(l):
    d = getattr(l, "dtype", None)
    return np.dtype(d) if d is not None else np.asarray(l).dtype


def _materialize(shape_tree):
    """Zeros matching a ShapeDtypeStruct tree (init needs runnable values)."""
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype) if isinstance(s, jax.ShapeDtypeStruct) else s,
        shape_tree,
    )
