"""Device synchronization barrier (the CUDA-event/stream-sync analog).

JAX dispatch is async; a device runs what it is handed in order, so blocking
on a trivial computation enqueued now drains the default device's queue.
Single source of truth used by timers, accelerator streams, and
accelerator.synchronize.
"""

from __future__ import annotations

import jax


def device_sync() -> None:
    (jax.device_put(0.0) + 0).block_until_ready()
