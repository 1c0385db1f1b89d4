"""ZeRO-Infinity streamed optimizer-state offload (host RAM ⇄ device).

The legacy path (``offload_states.py``) replaces the device optimizer with a
host AVX Adam: numerically close, but a different update rule — and every
step serializes host compute against the device. This module keeps the
EXISTING donated fused-step program family as the update engine and merely
changes where the fp32 master + Adam moments LIVE between steps: pinned host
buffers, streamed device-ward in ``bucket_size``-element buckets through a
depth-2 double-buffered async H2D pipeline (the PR-5 prefetch pattern with
host→device copies instead of all-gathers), updated per-bucket by a donated
jitted program, and streamed back D2H via ``copy_to_host_async`` while the
next bucket computes (PR-8's async-snapshot writer pattern in reverse).

Bit-identity is the contract: the per-bucket update program replays the
engine's ``step_fn``/``update_from_grads`` math op-for-op (scale, clip,
FusedAdam, mixed-precision recast), so offloaded losses, master tree and
fp16 scale trajectory bit-match the on-device path. The streamer itself
performs NO math — it is a buffer manager plus a transfer schedule.

Stream discipline (what the analysis/lint gates check):

* every H2D/D2H goes through the four sanctioned helpers — ``h2d_bucket``,
  ``d2h_bucket``, ``materialize_writes``, ``drain_writes`` — which count
  bytes and time; a host copy anywhere else in the step family is a
  DS-R009 lint error.
* ``stream_schedule()`` DECLARES each transfer and the compute program it
  hides behind; the ``overlap`` analysis pass verifies the declaration and
  reports ``exposed_stream_bytes`` (gated to 0 on the CI config). The
  ``pipeline_read`` / ``pipeline_write`` knobs are the levers: a transfer
  whose pipeline knob is off is declared (and measured) exposed.
* crash contract: host buffers are NEVER trusted across a crash — a kill
  mid-stream (``train.mid_offload_stream``) leaves them torn by design;
  resume rebuilds them from the last committed checkpoint
  (``load_state_dict``/``set_master_leaves``), bit-identically.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from deepspeed_tpu.utils.logging import log_dist

STREAMED_FORMAT = "streamed"


def split_offload_buckets(leaf_sizes: Sequence[int], bucket_size: int) -> List[List[int]]:
    """Greedy whole-leaf grouping: consecutive leaves pack into one bucket
    while the bucket stays under ``bucket_size`` elements; a single leaf
    larger than the budget gets its own bucket (leaves never split — the
    donated update programs are per-leaf)."""
    if bucket_size < 1:
        raise ValueError(f"bucket_size must be >= 1, got {bucket_size}")
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_elems = 0
    for i, n in enumerate(leaf_sizes):
        if cur and cur_elems + n > bucket_size:
            buckets.append(cur)
            cur, cur_elems = [], 0
        cur.append(i)
        cur_elems += n
    if cur:
        buckets.append(cur)
    return buckets


class HostOffloadStreamer:
    """Host-resident fp32 master + Adam moments, streamed per-bucket.

    Owns three host fp32 buffer sets (master, exp_avg, exp_avg_sq — one
    numpy array per param leaf), the bucket partition, the staged device
    copies of the in-flight buckets, and the pending D2H writebacks. With
    ``pin_memory`` the buffers are allocated once and written back in place
    (stable addresses — the TPU runtime can keep them registered); without
    it writebacks replace the array references.
    """

    def __init__(
        self,
        master_tree: Any,
        offload_config,
        *,
        mixed_precision: bool,
        clock=time.perf_counter,
    ):
        if jax.process_count() > 1:
            raise NotImplementedError(
                "streamed optimizer offload (offload_optimizer.pipeline_*) is "
                "single-process for now: the host buffers hold full leaves"
            )
        cfg = offload_config
        if float(getattr(cfg, "ratio", 1.0)) != 1.0:
            raise ValueError(
                "offload_optimizer.ratio < 1.0 is not supported on the streamed "
                "TPU path (all optimizer state offloads or none does)"
            )
        if int(getattr(cfg, "buffer_count", 0)) < 2:
            raise ValueError(
                "streamed optimizer offload runs a depth-2 double-buffered "
                "pipeline and needs offload_optimizer.buffer_count >= 2; got "
                f"{cfg.buffer_count}"
            )
        self.pin_memory = bool(getattr(cfg, "pin_memory", False))
        self.pipeline_read = bool(getattr(cfg, "pipeline_read", False))
        self.pipeline_write = bool(getattr(cfg, "pipeline_write", False))
        self.mixed_precision = bool(mixed_precision)
        self._clock = clock

        leaves, self.treedef = jax.tree_util.tree_flatten(master_tree)
        self._shardings = [l.sharding for l in leaves]
        self._shapes = [tuple(l.shape) for l in leaves]
        sizes = [int(np.prod(s)) if s else 1 for s in self._shapes]
        self._buckets = split_offload_buckets(sizes, int(cfg.bucket_size))

        # materialize the initial master on the host (PR-8 snapshot idiom:
        # enqueue every D2H first, then await — the copies pipeline)
        for l in leaves:
            copy_async = getattr(l, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
        # np.array(copy=True): device_get can return a VIEW of the device
        # buffer (CPU backend) that a later donated dispatch would clobber —
        # the host buffers must own their memory
        self._master = [np.array(jax.device_get(l), dtype=np.float32, copy=True) for l in leaves]
        self._exp_avg = [np.zeros_like(m) for m in self._master]
        self._exp_avg_sq = [np.zeros_like(m) for m in self._master]
        self.step_count = 0

        # in-flight state: staged H2D buckets and pending D2H writebacks
        self._staged: Dict[int, Tuple[Optional[list], list, list]] = {}
        self._pending: List[Tuple[int, list, list, list]] = []
        self._stats = {
            "h2d_ms": 0.0,
            "d2h_ms": 0.0,
            "exposed_ms": 0.0,
            "h2d_bytes": 0,
            "d2h_bytes": 0,
            "steps": 0,
        }
        n_bytes = 3 * sum(m.nbytes for m in self._master)
        log_dist(
            f"HostOffloadStreamer: {n_bytes / 1024**2:.1f} MB host state in "
            f"{len(self._buckets)} bucket(s) "
            f"(pin_memory={self.pin_memory}, pipeline_read={self.pipeline_read}, "
            f"pipeline_write={self.pipeline_write})",
            ranks=[0],
        )

    # -- bucket geometry ------------------------------------------------
    @property
    def num_buckets(self) -> int:
        return len(self._buckets)

    def bucket_indices(self, bi: int) -> List[int]:
        return self._buckets[bi]

    def _bucket_elems(self, bi: int) -> int:
        return sum(int(np.prod(self._shapes[i])) or 1 for i in self._buckets[bi])

    # -- sanctioned stream helpers --------------------------------------
    # These four methods are the ONLY places this class touches the device.
    # The DS-R009 lint extension flags device_put/device_get/
    # copy_to_host_async anywhere else in the stream method family.

    def h2d_bucket(self, bi: int) -> None:
        """Stage bucket ``bi`` device-ward (async ``device_put`` per leaf,
        sharded per the master shardings). With ``pipeline_read`` the copies
        overlap the in-flight compute; without it the call blocks — a
        deliberately exposed transfer the overlap gate turns red on."""
        if bi in self._staged:
            return
        # a pending writeback targeting this bucket must land first (only
        # reachable when num_buckets == 1: the deferred last-bucket D2H of
        # step N collides with step N+1's first upload)
        if any(p[0] == bi for p in self._pending):
            self.materialize_writes(keep=0)
        t0 = self._clock()
        ms = [jax.device_put(self._exp_avg[i], self._shardings[i]) for i in self._buckets[bi]]
        vs = [jax.device_put(self._exp_avg_sq[i], self._shardings[i]) for i in self._buckets[bi]]
        masters = None
        nbytes = sum(self._exp_avg[i].nbytes * 2 for i in self._buckets[bi])
        if self.mixed_precision:
            # fp32 training keeps master == params on device; only mixed
            # precision streams the fp32 master up
            masters = [jax.device_put(self._master[i], self._shardings[i]) for i in self._buckets[bi]]
            nbytes += sum(self._master[i].nbytes for i in self._buckets[bi])
        if not self.pipeline_read:
            for arr in (masters or []) + ms + vs:
                arr.block_until_ready()
        dt = (self._clock() - t0) * 1e3
        self._stats["h2d_ms"] += dt
        self._stats["h2d_bytes"] += nbytes
        if not self.pipeline_read:
            self._stats["exposed_ms"] += dt
        self._staged[bi] = (masters, ms, vs)

    def d2h_bucket(self, bi: int, new_master: list, new_m: list, new_v: list) -> None:
        """Enqueue bucket ``bi``'s updated master + moments host-ward
        (``copy_to_host_async`` — the PR-8 writer pattern in reverse: the
        copies drain while the NEXT bucket's update computes). Without
        ``pipeline_write`` the writeback materializes immediately (exposed)."""
        t0 = self._clock()
        for arr in list(new_master) + list(new_m) + list(new_v):
            copy_async = getattr(arr, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
        self._pending.append((bi, list(new_master), list(new_m), list(new_v)))
        nbytes = sum(self._master[i].nbytes * 3 for i in self._buckets[bi])
        self._stats["d2h_bytes"] += nbytes
        if not self.pipeline_write:
            self.materialize_writes(keep=0)
            dt = (self._clock() - t0) * 1e3
            self._stats["exposed_ms"] += dt
        else:
            dt = (self._clock() - t0) * 1e3
        self._stats["d2h_ms"] += dt

    def materialize_writes(self, keep: int = 0) -> None:
        """Land pending writebacks into the host buffers, oldest first,
        leaving at most ``keep`` in flight (``keep=1`` is the depth-2
        pipeline's steady state: the newest bucket's copies still overlap
        the next compute)."""
        t0 = self._clock()
        while len(self._pending) > keep:
            bi, masters, ms, vs = self._pending.pop(0)
            for k, i in enumerate(self._buckets[bi]):
                self._land(self._master, i, masters[k])
                self._land(self._exp_avg, i, ms[k])
                self._land(self._exp_avg_sq, i, vs[k])
        self._stats["d2h_ms"] += (self._clock() - t0) * 1e3

    def drain_writes(self) -> None:
        """Checkpoint fence: every pending writeback lands before the host
        buffers are snapshotted (a torn snapshot would otherwise mix step
        N and N-1 state)."""
        self.materialize_writes(keep=0)

    def _land(self, bufs: List[np.ndarray], i: int, arr) -> None:
        host = np.asarray(jax.device_get(arr), np.float32).reshape(self._shapes[i])
        if self.pin_memory:
            np.copyto(bufs[i], host)  # stable (pinned) buffer, write in place
        else:
            # own the memory: device_get may hand back a view of the (donated,
            # soon-reused) device buffer
            bufs[i] = np.array(host, dtype=np.float32, copy=True)

    # -- staged-bucket handoff ------------------------------------------
    def take_staged(self, bi: int) -> Tuple[Optional[list], list, list]:
        """Hand bucket ``bi``'s staged device arrays to the update program
        (which donates them). Requires a prior ``h2d_bucket(bi)``."""
        return self._staged.pop(bi)

    def discard_staged(self) -> None:
        """Drop every staged bucket (fp16 overflow: the step is skipped, the
        host state is already authoritative — nothing to write back)."""
        self._staged.clear()

    # -- declared transfer schedule (the overlap pass verifies this) ----
    def stream_schedule(self) -> Dict[str, Any]:
        """The stream's declared accounting: every per-step transfer, its
        bytes, and the compute program it hides behind (``None`` = exposed,
        which the gate counts). Mirrors the dispatch order of
        ``_take_streamed_offload_step``: buckets 0/1 upload under the tail
        of fwd/bwd, bucket i+2 uploads while bucket i updates, bucket i
        writes back while bucket i+1 updates, and the last writeback drains
        under the next step's fwd/bwd."""
        n = self.num_buckets
        per_elem_h2d = 12 if self.mixed_precision else 8  # fp32: moments only
        transfers = []
        for bi in range(n):
            if bi < 2:
                hide = "fwd_bwd"
            else:
                hide = f"offload_bucket_update_b{bi - 2}"
            transfers.append(
                {
                    "name": f"h2d_b{bi}",
                    "direction": "h2d",
                    "bytes": self._bucket_elems(bi) * per_elem_h2d,
                    "hide_behind": hide if self.pipeline_read else None,
                }
            )
        for bi in range(n):
            if bi < n - 1:
                hide = f"offload_bucket_update_b{bi + 1}"
            else:
                hide = "fwd_bwd"  # deferred: lands at the next step's fence
            transfers.append(
                {
                    "name": f"d2h_b{bi}",
                    "direction": "d2h",
                    "bytes": self._bucket_elems(bi) * 12,
                    "hide_behind": hide if self.pipeline_write else None,
                }
            )
        return {
            "anchor": "offload_stats",
            "compute_programs": ["fwd_bwd"]
            + [f"offload_bucket_update_b{bi}" for bi in range(n)],
            "transfers": transfers,
        }

    def stream_stats(self) -> Dict[str, Any]:
        out = dict(self._stats)
        out["buckets"] = self.num_buckets
        out["pending_writes"] = len(self._pending)
        return out

    # -- static residency accounting (analysis memory ledger) -----------
    def memory_report(self) -> Dict[str, Any]:
        """Byte-level residency contract for the HBM ledger: the master +
        both moments live in HOST RAM; the device only ever holds the
        staged upload of the bucket about to update plus the in-flight
        writeback of the bucket that just did — a ≤ 2-bucket bound,
        independent of model size. ``device_residency_bound_bytes`` is that
        static bound (the two largest buckets at the full 12-bytes/elem
        writeback footprint); ``staged_bytes``/``pending_bytes`` are the
        actual bytes on device right now."""
        per_elem_staged = 12 if self.mixed_precision else 8
        bucket_bytes = [
            self._bucket_elems(bi) * 12 for bi in range(self.num_buckets)
        ]
        bound = sum(sorted(bucket_bytes, reverse=True)[:2])
        staged = sum(
            self._bucket_elems(bi) * per_elem_staged for bi in self._staged
        )
        pending = sum(self._bucket_elems(p[0]) * 12 for p in self._pending)
        return {
            "master_location": "host",
            "host_bytes": 3 * sum(m.nbytes for m in self._master),
            "buckets": self.num_buckets,
            "bucket_bytes": bucket_bytes,
            "max_bucket_bytes": max(bucket_bytes, default=0),
            "device_residency_bound_bytes": bound,
            "staged_bytes": staged,
            "pending_bytes": pending,
            "device_bytes": staged + pending,
        }

    def note_step(self) -> None:
        self._stats["steps"] += 1

    # -- tree plumbing ---------------------------------------------------
    def unflatten(self, leaves: List[Any]):
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def master_leaves(self) -> List[np.ndarray]:
        """Host copies of the fp32 master (current through the write fence)."""
        self.drain_writes()
        return [m.copy() for m in self._master]

    # -- checkpoint surface (duck-typed to the engine's offload branch) --
    def state_dict(self) -> Dict[str, Any]:
        """Host-resident snapshot: the leaves are ALREADY numpy, so the
        async checkpoint writer persists them without any device round-trip
        (they pass through ``host_snapshot`` untouched). Copies — the live
        buffers keep training while the writer drains."""
        self.drain_writes()
        return {
            "format": STREAMED_FORMAT,
            "step": int(self.step_count),
            "leaves": [
                {
                    "master": self._master[i].copy(),
                    "exp_avg": self._exp_avg[i].copy(),
                    "exp_avg_sq": self._exp_avg_sq[i].copy(),
                }
                for i in range(len(self._master))
            ],
        }

    def _check_format(self, state: Dict[str, Any]) -> None:
        fmt = state.get("format") if isinstance(state, dict) else None
        if fmt != STREAMED_FORMAT:
            raise ValueError(
                "this checkpoint's host-offload state was saved by the legacy "
                f"per-shard offload engine (format={fmt!r}); the streamed "
                "engine cannot adopt it — load with "
                "offload_optimizer.pipeline_read/pipeline_write disabled, or "
                "pass load_optimizer_states=False to restart the moments"
            )

    def load_state_dict(self, state: Dict[str, Any]) -> None:
        """Rebuild the host buffers from a checkpoint. This is the ONLY
        sanctioned way to repopulate them after a crash — buffers torn by a
        mid-stream kill are never trusted."""
        self._check_format(state)
        self._staged.clear()
        self._pending.clear()
        self.step_count = int(state["step"])
        for i, rec in enumerate(state["leaves"]):
            np.copyto(self._master[i], np.asarray(rec["master"], np.float32))
            np.copyto(self._exp_avg[i], np.asarray(rec["exp_avg"], np.float32))
            np.copyto(self._exp_avg_sq[i], np.asarray(rec["exp_avg_sq"], np.float32))

    def load_master_only(self, state: Dict[str, Any]) -> None:
        """Module-only load: refresh the master, keep fresh moments."""
        self._check_format(state)
        for i, rec in enumerate(state["leaves"]):
            np.copyto(self._master[i], np.asarray(rec["master"], np.float32))

    def set_master_leaves(self, leaves: List[Any]) -> None:
        """Overwrite the host master from host/device arrays (adopting a
        non-offload checkpoint's master or module weights)."""
        self._staged.clear()
        self._pending.clear()
        for i, leaf in enumerate(leaves):
            np.copyto(
                self._master[i],
                np.asarray(jax.device_get(leaf), np.float32).reshape(self._shapes[i]),
            )
