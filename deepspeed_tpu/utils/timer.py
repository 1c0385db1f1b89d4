"""Wall-clock timers.

Counterpart of ``deepspeed/utils/timer.py`` (``SynchronizedWallClockTimer``,
``ThroughputTimer``). "Synchronized" here means blocking on JAX async dispatch
before reading the clock (the CUDA-event analog).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

FORWARD_MICRO_TIMER = "fwd_microstep"
FORWARD_GLOBAL_TIMER = "fwd"
BACKWARD_MICRO_TIMER = "bwd_microstep"
BACKWARD_GLOBAL_TIMER = "bwd"
STEP_MICRO_TIMER = "step_microstep"
STEP_GLOBAL_TIMER = "step"


from deepspeed_tpu.utils.sync import device_sync as _sync


class SynchronizedWallClockTimer:
    """Named host timers. ``tracer`` (``profiling/tracer.py``) routes every
    completed start/stop interval into the unified timeline as a span, so
    the wall-clock breakdown and the trace are one dataset.

    HOT-PATH HAZARD (fixed): ``Timer.stop`` used to default ``sync=True`` —
    a full device sync (drain of the async dispatch queue) on every stop,
    which serializes host and device and can dominate the step time. The default is now ``sync=False``; pass
    ``sync=True`` explicitly only OUTSIDE the step loop (window boundaries,
    benches — ``ThroughputTimer`` below is the sanctioned synced timer)."""

    class Timer:
        def __init__(self, name: str, tracer=None):
            self.name = name
            self.tracer = tracer
            self.started = False
            self.start_time = 0.0
            self.elapsed_ = 0.0
            self.record = []

        def start(self, sync: bool = False):
            if sync:
                _sync()
            self.start_time = time.perf_counter()
            self.started = True

        def stop(self, sync: bool = False, record: bool = False):
            if not self.started:
                return
            if sync:
                _sync()
            now = time.perf_counter()
            self.elapsed_ += now - self.start_time
            self.started = False
            if record:
                self.record.append(self.elapsed_)
            if self.tracer is not None:
                self.tracer.add_span(f"timer.{self.name}", self.start_time, now)

        def reset(self):
            self.elapsed_ = 0.0
            self.started = False

        def elapsed(self, reset: bool = True) -> float:
            out = self.elapsed_
            if reset:
                self.reset()
            return out

        def mean(self) -> float:
            return sum(self.record) / len(self.record) if self.record else 0.0

    def __init__(self, tracer=None):
        self.timers: Dict[str, SynchronizedWallClockTimer.Timer] = {}
        self.tracer = tracer

    def __call__(self, name: str) -> "SynchronizedWallClockTimer.Timer":
        if name not in self.timers:
            self.timers[name] = self.Timer(name, tracer=self.tracer)
        return self.timers[name]

    def log(self, names: List[str], normalizer: float = 1.0, reset: bool = True, memory_breakdown=None, ranks=None):  # noqa: ARG002
        from deepspeed_tpu.utils.logging import log_dist

        parts = []
        for name in names:
            if name in self.timers:
                elapsed = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                parts.append(f"{name}: {elapsed:.2f}")
        log_dist("time (ms) | " + " | ".join(parts), ranks=ranks or [0])

    def get_mean(self, names: List[str], normalizer: float = 1.0) -> Dict[str, float]:
        return {n: self.timers[n].mean() * 1000.0 / normalizer for n in names if n in self.timers}


class NoopTimer:
    class Timer:
        def start(self, *a, **k):
            pass

        def stop(self, *a, **k):
            pass

        def reset(self):
            pass

        def elapsed(self, *a, **k):
            return 0.0

    def __call__(self, name):  # noqa: ARG002
        return self.Timer()

    def log(self, *a, **k):
        pass


class ThroughputTimer:
    """Samples/sec reporting (reference ``ThroughputTimer``).

    The reference synchronizes the accelerator around EVERY step to time it
    (cheap on a local CUDA stream). Here a sync drains the async dispatch
    queue — on TPU that serializes host and device and can dominate the
    step time. So this timer measures whole
    *logging windows* instead: it syncs once per ``steps_per_output`` steps,
    divides wall-clock by the window's sample count, and leaves the hot loop
    fully async. Steady-state numbers are identical; only sub-window
    per-step resolution is given up.
    """

    def __init__(self, batch_size: int, start_step: int = 2, steps_per_output: int = 50, monitor_memory: bool = False, logging_fn=None):
        self.batch_size = max(batch_size, 1)
        self.start_step = start_step
        self.steps_per_output = max(steps_per_output, 1)
        self.monitor_memory = monitor_memory
        self.logging = logging_fn
        self.epoch_count = 0
        self.micro_step_count = 0
        self.global_step_count = 0
        self.total_elapsed_time = 0.0
        self.started = False
        self.initialized = False
        self._window_open = False
        self._window_start_time = 0.0
        self._window_start_step = 0
        self._measured_steps = 0
        self._last_window_rate = 0.0

    def update_epoch_count(self):
        self.epoch_count += 1
        self.micro_step_count = 0

    def abort_window(self):
        """Discard a half-open measurement window (e.g. the engine switches
        to eval mid-window) so its wall-clock never deflates the rate."""
        self._window_open = False

    def start(self):
        self.started = True
        if not self._window_open and self.global_step_count >= self.start_step:
            # open a measurement window on a drained queue: host work between
            # windows (checkpoint saves, eval loops) is not counted
            _sync()
            self._window_start_time = time.perf_counter()
            self._window_start_step = self.global_step_count
            self._window_open = True
            self.initialized = True

    def stop(self, global_step: bool = False, report_speed: bool = True):
        if not self.started:
            return
        self.started = False
        self.micro_step_count += 1
        if global_step:
            self.global_step_count += 1
        if not (self._window_open and global_step):
            return
        window_steps = self.global_step_count - self._window_start_step
        if window_steps < self.steps_per_output and self.global_step_count % self.steps_per_output != 0:
            return
        _sync()
        now = time.perf_counter()
        duration = now - self._window_start_time
        self.total_elapsed_time += duration
        self._measured_steps += window_steps
        if duration > 0:
            self._last_window_rate = self.batch_size * window_steps / duration
        if report_speed and self.logging:
            self.logging(
                f"epoch={self.epoch_count}/micro_step={self.micro_step_count}/"
                f"global_step={self.global_step_count}, RunningAvgSamplesPerSec={self.avg_samples_per_sec():.2f}, "
                f"CurrSamplesPerSec={self._last_window_rate:.2f}"
            )
        self._window_open = False

    def avg_samples_per_sec(self) -> float:
        if self.total_elapsed_time > 0 and self._measured_steps > 0:
            return self.batch_size * self._measured_steps / self.total_elapsed_time
        return 0.0
