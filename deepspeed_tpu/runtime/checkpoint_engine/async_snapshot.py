"""Async atomic checkpointing: snapshot device→host, persist in background.

The reference's Nebula engine
(``runtime/checkpoint_engine/nebula_checkpoint_engine.py``) hides checkpoint
persistence behind training compute by snapshotting to host memory and
writing from a service thread. This is that design realized TPU-natively,
with the same hiding discipline as the PR-5 prefetch pipeline:

* ``host_snapshot`` enqueues **every leaf's D2H copy first**
  (``copy_to_host_async``) and only then materializes them — the transfers
  overlap each other instead of serializing one ``device_get`` at a time.
  This is the ONLY on-step cost (the ``ckpt_stall_ms`` the bench records):
  it must complete before returning because the step programs donate the
  state tuple, so the next dispatch would invalidate the source buffers.
* the snapshot is handed to a background writer thread that runs the staged
  atomic save (``orbax_checkpoint_engine.py``), the commit rename, and the
  ``latest`` marker update — disk latency never blocks the step loop.
* **double-buffered**: up to ``max_inflight`` snapshots may be queued; a
  save beyond that waits for the oldest write to drain (bounding host RAM
  at ``max_inflight`` state copies). No jitted program is involved anywhere
  — compile/dispatch telemetry shows zero new programs on the hot path.

Crash semantics: the writer thread catches ``Exception`` (surfaced at the
next ``submit``/``wait`` fence) but NOT ``BaseException`` — a chaos
``ChaosKilled`` kills the thread mid-write exactly like a real ``kill -9``,
leaving staged-but-uncommitted garbage that the atomic layout is designed to
survive.
"""

from __future__ import annotations

import atexit
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import numpy as np

from deepspeed_tpu.runtime.checkpoint_engine.atomic import write_latest_marker
from deepspeed_tpu.utils.logging import logger

# Exit-drain plumbing. A clean interpreter exit must flush every queued
# snapshot, and the WHERE is delicate: the writer persists through orbax,
# which schedules work on concurrent.futures executors that are shut down
# by a threading._register_atexit callback (runs in REVERSE registration
# order, before non-daemon threads are joined, before classic atexit). So
# the drain is registered via the same hook, LAZILY at first writer
# creation — later registration = earlier execution, i.e. before the
# executors close. Classic atexit is far too late (new threads cannot
# start during finalization; an orbax join there hangs forever).
_LIVE_WRITERS: "weakref.WeakSet" = weakref.WeakSet()
_DRAIN_REGISTERED = False


def _drain_live_writers() -> None:
    for writer in list(_LIVE_WRITERS):
        try:
            writer.wait()
        except Exception as e:
            logger.error(f"checkpoint writer drain at exit failed: {e}")


def _register_exit_drain() -> None:
    global _DRAIN_REGISTERED
    if _DRAIN_REGISTERED:
        return
    _DRAIN_REGISTERED = True
    register = getattr(threading, "_register_atexit", None)
    if register is not None:  # CPython 3.9+
        register(_drain_live_writers)
    else:  # best effort; the non-daemon worker is the real backstop here
        atexit.register(_drain_live_writers)


def host_snapshot(tree: Any) -> Any:
    """Materialize a state pytree on the host. All D2H copies are enqueued
    before any is awaited, so the transfers pipeline; non-array leaves
    (counters, config dicts) pass through untouched. Returns a tree of
    numpy arrays + plain python values, safe to hand to another thread
    while the donating step programs keep running."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    for leaf in leaves:
        if isinstance(leaf, jax.Array):
            leaf.copy_to_host_async()
    host = [
        np.asarray(jax.device_get(leaf)) if isinstance(leaf, jax.Array) else leaf
        for leaf in leaves
    ]
    return jax.tree_util.tree_unflatten(treedef, host)


def tree_fully_addressable(tree: Any) -> bool:
    """True when every jax leaf is locally materializable — the async path's
    precondition (a cross-process global array has no single-host copy; its
    save must go through the collective orbax path synchronously)."""
    return all(
        leaf.is_fully_addressable
        for leaf in jax.tree_util.tree_leaves(tree)
        if isinstance(leaf, jax.Array)
    )


@dataclass
class _Job:
    state: Any
    path: str
    tag: str
    save_dir: Optional[str]  # None = skip the latest-marker update
    done: threading.Event = field(default_factory=threading.Event)


class AsyncCheckpointWriter:
    """Background persister over a (staged, atomic) checkpoint engine."""

    def __init__(self, inner, max_inflight: int = 2, tracer=None):
        if max_inflight < 1:
            raise ValueError(f"max_inflight must be >= 1, got {max_inflight}")
        self.inner = inner
        self.max_inflight = int(max_inflight)
        # unified-tracing hookup: the writer thread records ckpt.stage /
        # ckpt.commit spans onto the ENGINE's tracer — the tracer's ring
        # buffer and nesting state are thread-safe by contract (the tracer
        # test suite exercises exactly this writer)
        from deepspeed_tpu.profiling.tracer import NULL_TRACER

        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._jobs: deque = deque()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[Exception] = None
        self.saves = 0
        self.last_save_s = 0.0
        _LIVE_WRITERS.add(self)
        _register_exit_drain()

    # --- public surface -------------------------------------------------
    def submit(self, host_state: Any, path: str, tag: str, save_dir: Optional[str]) -> None:
        """Queue one snapshot for persistence. Blocks only while
        ``max_inflight`` older writes are still draining."""
        self._raise_pending_error()
        job = _Job(state=host_state, path=path, tag=tag, save_dir=save_dir)
        while True:
            with self._lock:
                self._reap_locked()
                if self._thread is not None and not self._thread.is_alive() and self._jobs:
                    # the writer died mid-queue (a chaos kill): the remaining
                    # jobs will never drain — drop them so the caller is not
                    # wedged behind a dead thread
                    self._jobs.clear()
                if len(self._jobs) < self.max_inflight:
                    self._jobs.append(job)
                    self._ensure_worker_locked()
                    return
                oldest = self._jobs[0]
            oldest.done.wait(timeout=0.05)

    def wait(self) -> None:
        """Fence: block until every queued write has committed (or the
        writer died), then surface any persist error."""
        while True:
            with self._lock:
                self._reap_locked()
                if not self._jobs:
                    break
                job = self._jobs[0]
                dead = self._thread is None or not self._thread.is_alive()
            if dead:
                with self._lock:
                    self._jobs.clear()
                break
            job.done.wait(timeout=0.05)
        self._raise_pending_error()

    def pending(self) -> int:
        with self._lock:
            self._reap_locked()
            return len(self._jobs)

    # --- internals -------------------------------------------------------
    def _raise_pending_error(self) -> None:
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(f"async checkpoint persist failed: {err}") from err

    def _reap_locked(self) -> None:
        while self._jobs and self._jobs[0].done.is_set():
            self._jobs.popleft()

    def _ensure_worker_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            # NON-daemon and alive only while the queue is non-empty: even
            # without the _register_atexit drain, threading._shutdown's
            # non-daemon join waits out an in-flight write. Abrupt deaths
            # are untouched — SIGKILL/os._exit skip every join.
            self._thread = threading.Thread(
                target=self._run, name="ckpt-writer", daemon=False
            )
            self._thread.start()

    def _run(self) -> None:
        while True:
            with self._lock:
                self._reap_locked()
                if not self._jobs:
                    # drained: exit; submit() restarts the worker on demand
                    self._thread = None
                    return
                job = self._jobs[0]
            try:
                t0 = time.perf_counter()
                with self.tracer.span("ckpt.stage", tag=job.tag):
                    self.inner.save(job.state, job.path)
                with self.tracer.span("ckpt.commit", tag=job.tag):
                    self.inner.commit(job.tag)
                    if job.save_dir is not None:
                        write_latest_marker(job.save_dir, job.tag)
                self.last_save_s = time.perf_counter() - t0
                self.saves += 1
            except Exception as e:  # surfaced at the next fence
                self._error = e
                logger.error(f"async checkpoint persist failed: {e}")
            except BaseException:
                # a chaos/interpreter kill mid-write: THIS write dies like
                # the process would — torn staged state stays on disk, no
                # error is recorded. Queued later snapshots are independent
                # saves, so a replacement worker picks them up (only the
                # killed write is lost, matching a single torn save).
                job.done.set()
                with self._lock:
                    self._thread = None
                    self._reap_locked()
                    if self._jobs:
                        self._ensure_worker_locked()
                return
            job.done.set()
