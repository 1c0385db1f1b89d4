"""Dataloader.

Counterpart of ``deepspeed/runtime/dataloader.py`` (``DeepSpeedDataLoader``
with ``DistributedSampler``). TPU-native behavior: batches are *global* —
the engine shards the leading dim over the dense-DP mesh axes at
``device_put`` time — so the sampler's job is only per-process slicing of the
global batch when running multi-host (each host loads its addressable slice).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Iterable, Optional

import numpy as np


def _default_collate(items):
    first = items[0]
    if isinstance(first, dict):
        return {k: _default_collate([it[k] for it in items]) for k in first}
    if isinstance(first, (tuple, list)):
        return type(first)(_default_collate([it[i] for it in items]) for i in range(len(first)))
    return np.stack([np.asarray(it) for it in items])


class RepeatingLoader:
    """Wraps an iterator to restart on StopIteration (reference pipe utils).

    Carries a resumable cursor for exact-resume checkpointing: when the
    wrapped loader exposes ``state_dict``/``load_state_dict`` (as
    ``DeepSpeedDataLoader`` does) the inner cursor is delegated to;
    otherwise the served-batch count is recorded and replayed best-effort."""

    def __init__(self, loader):
        self.loader = loader
        self.data_iter = iter(self.loader)
        self.batches_served = 0

    def __iter__(self):
        return self

    def __next__(self):
        try:
            batch = next(self.data_iter)
        except StopIteration:
            self.data_iter = iter(self.loader)
            batch = next(self.data_iter)
        self.batches_served += 1
        return batch

    def state_dict(self):
        sd = {"batches_served": self.batches_served}
        if hasattr(self.loader, "state_dict"):
            sd["loader"] = self.loader.state_dict()
        return sd

    def load_state_dict(self, sd) -> None:
        self.batches_served = int(sd.get("batches_served", 0))
        if "loader" in sd and hasattr(self.loader, "load_state_dict"):
            self.loader.load_state_dict(sd["loader"])
            self.data_iter = iter(self.loader)
            return
        # opaque inner iterable: replay from the start (deterministic
        # loaders land on the same cursor; anything else cannot be resumed
        # exactly and should expose state_dict itself). Replay restarts on
        # exhaustion exactly like __next__ — batches_served is cumulative
        # across wraparounds, so an unsized loader replays whole passes.
        self.data_iter = iter(self.loader)
        try:
            n = len(self.loader)
        except TypeError:
            n = 0
        for _ in range(self.batches_served % n if n else self.batches_served):
            try:
                next(self.data_iter)
            except StopIteration:
                self.data_iter = iter(self.loader)
                next(self.data_iter)


class DeepSpeedDataLoader:
    def __init__(
        self,
        dataset,
        batch_size: int,
        collate_fn: Optional[Callable] = None,
        shuffle: bool = False,
        seed: int = 0,
        drop_last: bool = True,
        num_local_io_workers: Optional[int] = None,  # noqa: ARG002 - API parity
        data_sampler=None,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.collate_fn = collate_fn or _default_collate
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.post_process_func = None
        self.data_sampler = data_sampler
        self.epoch = 0
        # resumable data cursor (exact-resume checkpointing): batches
        # yielded in the current epoch, saved via state_dict and consumed
        # ONCE by the next __iter__ after load_state_dict
        self._cursor = 0
        self._resume_cursor = 0
        try:
            self._len = len(dataset)
        except TypeError:
            self._len = None

    def __len__(self) -> int:
        if self._len is None:
            raise TypeError("dataset has no length")
        if self.drop_last:
            return self._len // self.batch_size
        return math.ceil(self._len / self.batch_size)

    def set_epoch(self, epoch: int) -> None:
        """Select the epoch; cursors reset only when it actually CHANGES.
        The canonical resumed loop calls ``set_epoch(current_epoch)`` right
        after ``load_checkpoint`` — that must not wipe the restored
        mid-epoch cursor, or the resumed run silently re-serves already
        trained batches."""
        if epoch != self.epoch:
            self._cursor = 0
            self._resume_cursor = 0
        self.epoch = epoch

    def state_dict(self) -> dict:
        """The data cursor: where in which epoch the loader stands. Saved
        into checkpoints so an ``auto_resume`` run replays the EXACT batch
        sequence an uninterrupted run would have seen."""
        return {"epoch": self.epoch, "cursor": self._cursor}

    def load_state_dict(self, sd: dict) -> None:
        self.epoch = int(sd.get("epoch", 0))
        self._cursor = int(sd.get("cursor", 0))
        self._resume_cursor = self._cursor

    def _indices(self):
        n = self._len
        order = np.arange(n)
        if self.data_sampler is not None:
            order = np.asarray(list(iter(self.data_sampler)))
        elif self.shuffle:
            rng = np.random.default_rng(self.seed + self.epoch)
            rng.shuffle(order)
        return order

    def __iter__(self):
        start, self._resume_cursor = self._resume_cursor, 0
        if self._len is None:
            # iterable dataset: batch on the fly (resume = deterministic
            # replay past the already-consumed batches)
            for b, batch in enumerate(self._iter_stream()):
                if b < start:
                    continue
                self._cursor = b + 1
                yield self._post(batch)
            self.epoch += 1
            self._cursor = 0
            return
        order = self._indices()
        n_batches = len(self)
        for b in range(min(start, n_batches), n_batches):
            idx = order[b * self.batch_size : (b + 1) * self.batch_size]
            items = [self.dataset[int(i)] for i in idx]
            self._cursor = b + 1
            yield self._post(self.collate_fn(items))
        # a completed pass rolls the cursor into the next epoch, so a
        # RepeatingLoader's wraparound is captured in the saved state
        self.epoch += 1
        self._cursor = 0

    def _post(self, batch):
        """Data-efficiency hook (reference engine.set_data_post_process_func
        -> dataloader.post_process_func): applied to each emitted batch."""
        return self.post_process_func(batch) if self.post_process_func else batch

    def _iter_stream(self):
        buf = []
        for item in self.dataset:
            buf.append(item)
            if len(buf) == self.batch_size:
                yield self.collate_fn(buf)
                buf = []
        if buf and not self.drop_last:
            yield self.collate_fn(buf)
