"""Device-mesh topology.

TPU-native counterpart of the reference's process-group topology layer
(``deepspeed/utils/groups.py:51-528`` and
``deepspeed/runtime/pipe/topology.py:12`` ``ProcessTopology``): instead of
materializing NCCL communicators per group, we build one
``jax.sharding.Mesh`` whose named axes *are* the groups, and every collective
is expressed against an axis name.

Axis layout (outer→inner): ``pipe, data, expert, sequence, model``.

* dense data-parallel (and ZeRO sharding) runs over the **combined**
  ``(data, expert)`` axes — the reference's ``expert_data_parallel`` group —
  so MoE with ``expert>1`` regroups part of DP into EP exactly like
  ``groups._create_expert_and_data_parallel`` (groups.py:113).
* ``model`` is innermost so TP collectives ride the shortest ICI hops;
  ``pipe`` is outermost so stage boundaries cross the slowest links only
  once per microbatch.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from deepspeed_tpu.runtime.config import MeshConfig

# canonical axis order, outermost first
AXIS_ORDER: Tuple[str, ...] = ("pipe", "data_outer", "data", "expert", "sequence", "model")

_TOPOLOGY: Optional["Topology"] = None


class Topology:
    """A named-axis device mesh + the reference's group-accessor surface."""

    def __init__(self, mesh, mesh_config: MeshConfig):
        self.mesh = mesh
        self.config = mesh_config

    # --- world sizes (reference groups.py accessors) -------------------
    def get_data_parallel_world_size(self) -> int:
        """Dense DP world = data_outer × data × expert (the expert_data group)."""
        return self.config.data_outer * self.config.data * self.config.expert

    def get_expert_parallel_world_size(self) -> int:
        return self.config.expert

    def get_expert_data_parallel_world_size(self) -> int:
        return self.config.data

    def get_model_parallel_world_size(self) -> int:
        return self.config.model

    def get_sequence_parallel_world_size(self) -> int:
        return self.config.sequence

    def get_sequence_data_parallel_world_size(self) -> int:
        return self.config.sequence * self.get_data_parallel_world_size()

    def get_pipe_parallel_world_size(self) -> int:
        return self.config.pipe

    @property
    def world_size(self) -> int:
        return int(np.prod([
            self.config.pipe, self.config.data_outer, self.config.data,
            self.config.expert, self.config.sequence, self.config.model,
        ]))

    # --- axis-name groups ----------------------------------------------
    @property
    def data_parallel_axes(self) -> Tuple[str, ...]:
        """Axes a dense gradient reduction runs over (includes sequence: each
        sequence shard sees a slice of the batch's tokens, so grads reduce over
        seq too — mirroring the reference's seq_data group, engine.py:1111)."""
        axes = ["data"]
        if self.config.data_outer > 1:
            axes.insert(0, "data_outer")
        if self.config.expert > 1:
            axes.append("expert")
        if self.config.sequence > 1:
            axes.append("sequence")
        return tuple(axes)

    @property
    def zero_shard_axes(self) -> Tuple[str, ...]:
        """Axes ZeRO partitions params/opt-state over: the dense DP axes
        MINUS the MiCS replication axis — with data_outer > 1, state shards
        only within each sub-group and replicates across groups
        (reference mics.py shard-group semantics)."""
        return tuple(a for a in self.data_parallel_axes if a != "data_outer")

    @property
    def expert_parallel_axis(self) -> str:
        return "expert"

    @property
    def model_parallel_axis(self) -> str:
        return "model"

    @property
    def sequence_parallel_axis(self) -> str:
        return "sequence"

    @property
    def pipe_parallel_axis(self) -> str:
        return "pipe"

    def axis_size(self, name: str) -> int:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))[name]

    def dense_batch_axes(self):
        """Mesh axes the batch's leading dim is sharded over, normalized to
        None | str | tuple — the single source for batch PartitionSpec entries
        (used by the engine's batch placement and the SP attention specs)."""
        axes = tuple(a for a in ("data_outer", "data", "expert") if self.axis_size(a) > 1)
        if not axes:
            return None
        if len(axes) == 1:
            return axes[0]
        return axes


def mesh_fingerprint(mesh) -> tuple:
    """Hashable identity of a Mesh — axis names, shape, and the flat
    device ids. The ONE definition shared by every cache that must not
    serve an executable (or an out_shardings contract) built for one mesh
    to arrays living on another: the paged-program cache key
    (``inference/tp.py:TPServing.cache_key``) and the pool's CoW copier
    cache (``inference/kv_pool.py``)."""
    return (
        tuple(mesh.axis_names),
        tuple(int(s) for s in mesh.devices.shape),
        tuple(d.id for d in mesh.devices.flat),
    )


def build_mesh(
    mesh_config: MeshConfig,
    devices: Optional[List] = None,
) -> Topology:
    """Create the global Mesh from resolved axis sizes.

    Uses ``mesh_utils.create_device_mesh`` so the logical axes map onto the
    physical ICI torus (innermost logical axis → nearest neighbors); for
    CPU devices that call is itself a row-major reshape. A shape it cannot
    map onto a TPU's topology raises.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    resolved = mesh_config.resolve(n)
    shape = (
        resolved.pipe,
        resolved.data_outer,
        resolved.data,
        resolved.expert,
        resolved.sequence,
        resolved.model,
    )
    dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    mesh = Mesh(dev_array, AXIS_ORDER)
    return Topology(mesh, resolved)


def build_serving_mesh(tp_degree: int, devices: Optional[List] = None) -> Topology:
    """Full-world topology with a ``model=tp_degree`` axis (innermost — TP
    all-reduces ride the shortest ICI hops), everything else folded into
    ``data``. ``InferenceEngine.__init__`` re-meshes through this when
    ``tensor_parallel.tp_size`` asks for a model axis the live topology
    does not have (it drives the dense AutoTP forward/generate path). The
    PAGED serving programs instead run on a compact 1-D submesh of the
    first ``tp_degree`` devices (``inference/tp.py:serving_mesh``) — one
    TP group; the devices this topology folds into ``data`` are the fleet
    layer's replica budget."""
    import jax

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if tp_degree < 1 or n % tp_degree:
        raise ValueError(
            f"tp_size={tp_degree} must be >= 1 and divide the {n} visible devices"
        )
    return build_mesh(MeshConfig(model=tp_degree, data=n // tp_degree), devices)


def initialize_topology(mesh_config: Optional[MeshConfig] = None, devices=None) -> Topology:
    global _TOPOLOGY
    _TOPOLOGY = build_mesh(mesh_config or MeshConfig(), devices)
    return _TOPOLOGY


def get_topology() -> Topology:
    if _TOPOLOGY is None:
        return initialize_topology()
    return _TOPOLOGY


def set_topology(topology: Optional[Topology]) -> None:
    global _TOPOLOGY
    _TOPOLOGY = topology


def reset_topology() -> None:
    set_topology(None)
