"""Unified tracing & metrics plane (ISSUE 10).

Five telemetry surfaces grew up siloed — ``compile_stats()``,
``analysis_report()``, ``serve_stats()``, ``checkpoint_stats()``, and the
bench fields — and none of them can answer "where did step N's 11 ms go?"
or "what was the server doing in the 200 ms before it died?". This module
is the shared timeline + metrics substrate underneath all of them:

* :class:`Tracer` — span-based structured tracing. Spans are **host-side
  only** (monotonic ``time.perf_counter`` stamps around host phases; device
  time is inferred from the dispatch-enqueue and blocking-fetch boundaries
  the engines already have), nest via a per-thread stack, and land in a
  bounded ring buffer (``collections.deque(maxlen=...)``) so a long-running
  server holds the LAST window of activity, not an unbounded log. Appends
  are lock-guarded and the per-thread nesting state is ``threading.local``,
  so the async checkpoint writer and the serving loop can trace
  concurrently. The hard hot-path contract (enforced by the
  telemetry-is-free tests): tracing performs **zero host↔device transfers
  and compiles zero new programs** — nothing in this file imports jax.
  ``Tracer.sink`` is the spans' second destination: the engines (which do
  import jax) set it to ``jax.profiler.TraceAnnotation``, so that every
  ``span()`` is also an event of the profiler's own trace, **on the clock
  the device ops are on**, with its attributes as the event's stats. The
  ring buffer is what the flight recorder, ``phase_summary`` and the
  Chrome export read; the profiler's trace is what ``benchmark/`` reads.
  ``add_span`` (explicit stamps: ``timer.*``, ``comm.*``, ``infer.*``),
  instants and the asynchronous request lifecycles cannot be annotations
  (an annotation is a scope on one thread) and stay ring-buffer only.
* :class:`MetricsRegistry` — named counters / gauges / fixed-bucket
  histograms (p50/p99 via bucket interpolation), thread-safe, cheap enough
  for per-step observation.
* Chrome-trace export — :meth:`Tracer.export_chrome_trace` writes the
  Trace Event Format JSON that Perfetto / ``chrome://tracing`` load
  directly: complete (``X``) events for spans, instant (``i``) events,
  async (``b``/``n``/``e``) events for request lifecycles.
* :class:`FlightRecorder` — the crash postmortem: dump the ring buffer +
  open spans + metrics snapshot to a JSON file on ``atexit``, on a signal,
  or on a ``utils/chaos.py`` fault injection (the chaos kill hook fires
  before ``os._exit``/``ChaosKilled``, so every fault-injection kill from
  the PR-8 matrix leaves a parseable postmortem naming the armed point).
* :class:`ObservabilityHub` — the one-call merge: ``engine.observability()``
  returns the timeline + metrics next to the engine's existing stat
  surfaces (compile / analysis / serve / checkpoint), and
  :meth:`ObservabilityHub.monitor_events` turns the current metrics into
  the ``(name, value, step)`` events the ``monitor/`` backends fan out.
  The fleet router (``inference/fleet.py`` — the other module bound by
  this file's never-import-jax contract, lint DS-R010) traces its own
  span family on the same timeline (``fleet.step`` > ``fleet.replica_step``
  per replica, plus ``fleet.route`` / ``fleet.migrate`` / ``fleet.drain``
  and ``fleet.replica_dead`` / ``fleet.join`` instants) and registers a
  ``fleet`` source via ``FleetRouter.attach_observability(hub)``, so one
  report shows the router's supervision next to each replica's serving
  phases.

Overhead discipline: a disabled tracer's ``span()`` returns a shared no-op
context manager (one attribute read + one call); an enabled span costs two
clock reads, one small dict, and one lock-guarded deque append — single-digit
microseconds against multi-millisecond steps. The guard test pins the
measured overhead under 2% of a bench-like step.
"""

from __future__ import annotations

import atexit
import bisect
import json
import os
import signal as _signal
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "Tracer",
    "NULL_TRACER",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "FlightRecorder",
    "ObservabilityHub",
]


def _atomic_json_dump(path: str, payload) -> str:
    """Temp + fsync + rename JSON write: a concurrent reader (or a crash
    mid-dump) never sees a torn file. Local on purpose — this module must
    not import ``runtime/checkpoint_engine/atomic.py`` (the tracer's
    no-jax-import constraint is load-bearing)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(payload, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return path


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
class _NullSpan:
    """Shared no-op span: what a disabled tracer hands out. Duration reads
    0 so callers deriving timings from it must check ``tracer.enabled``."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> "_NullSpan":  # noqa: ARG002
        return self

    @property
    def duration_ms(self) -> float:
        return 0.0


_NULL_SPAN = _NullSpan()


class _Span:
    """A live span: context manager that stamps perf_counter on entry/exit,
    tracks nesting depth through the tracer's per-thread stack, and appends
    one completed record to the ring buffer on exit."""

    __slots__ = ("_tr", "name", "attrs", "t0", "t1", "depth", "_ann")

    def __init__(self, tracer: "Tracer", name: str, attrs: Optional[Dict]):
        self._tr = tracer
        self.name = name
        self.attrs = attrs
        self.t0 = 0.0
        self.t1 = 0.0
        self.depth = 0
        self._ann = None  # the sink's scope for this span, while it is open

    def __enter__(self) -> "_Span":
        tr = self._tr
        stack = tr._stack()
        self.depth = len(stack)
        stack.append(self)  # the stack IS the open-span registry (no lock)
        sink = tr.sink
        if sink is not None:
            # outside a profiler session this is one inactive-TraceMe check
            ann = self._ann = sink(self.name, **self.attrs) if self.attrs else sink(self.name)
            ann.__enter__()
        self.t0 = tr.clock()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tr
        self.t1 = tr.clock()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        stack = tr._stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # unbalanced exit (exception unwound past us)
            stack.remove(self)
        tr._append(
            {
                "ph": "X",
                "name": self.name,
                "t0": self.t0,
                "t1": self.t1,
                "tid": threading.get_ident(),
                "depth": self.depth,
                "attrs": self.attrs,
            }
        )
        return False

    def set(self, **attrs) -> "_Span":
        """Attach/overwrite attributes mid-span (e.g. a row count known only
        after packing)."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        return self

    @property
    def duration_ms(self) -> float:
        return (self.t1 - self.t0) * 1e3


class Tracer:
    """Span/event recorder over a bounded ring buffer.

    ``enabled=False`` makes every recording call a near-free no-op (the
    shared :data:`_NULL_SPAN` / an early return); flipping ``enabled`` at
    runtime is safe (the bench uses it to measure tracing overhead).
    ``clock`` is injectable for tests; it must be monotonic. ``sink``
    (attribute, default None) is called as ``sink(name, **attrs)`` for every
    ``span()`` and must return a context manager with
    ``set_metadata(**attrs)``: the engines set it to
    ``jax.profiler.TraceAnnotation``, which this module may not import.
    """

    def __init__(
        self,
        max_spans: int = 4096,
        enabled: bool = True,
        clock: Callable[[], float] = time.perf_counter,
    ):
        if max_spans < 1:
            raise ValueError(f"max_spans must be >= 1, got {max_spans}")
        self.enabled = bool(enabled)
        self.clock = clock
        self.sink: Optional[Callable[..., Any]] = None
        self.max_spans = int(max_spans)
        self._buf: deque = deque(maxlen=self.max_spans)
        self._total = 0
        self._lock = threading.Lock()
        self._local = threading.local()
        # tid -> that thread's open-span stack: the per-thread nesting state
        # doubles as the open-span registry (open_spans() walks these), so
        # span enter/exit pays ZERO lock acquisitions — only the completed-
        # record append takes the lock
        self._stacks: Dict[int, List[_Span]] = {}
        # wall-clock anchor so exported traces carry absolute timestamps
        self._anchor = (time.time(), self.clock())

    # --- internals ------------------------------------------------------
    def _stack(self) -> List[_Span]:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
            with self._lock:
                self._stacks[threading.get_ident()] = st
        return st

    def _append(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            self._buf.append(rec)
            self._total += 1

    # --- recording surface ----------------------------------------------
    def span(self, name: str, **attrs):
        """Context manager timing one host-side phase. Nest freely; the
        record carries the nesting depth and thread id."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs or None)

    def add_span(self, name: str, t0: float, t1: float, **attrs) -> None:
        """Record a span from explicit clock() stamps (the timer module and
        the comm wrappers route through this — they own their own timing).
        Ring buffer only: a scope that has already ended cannot be handed to
        ``sink``."""
        if not self.enabled:
            return
        self._append(
            {
                "ph": "X",
                "name": name,
                "t0": t0,
                "t1": t1,
                "tid": threading.get_ident(),
                "depth": len(self._stack()),
                "attrs": attrs or None,
            }
        )

    def event(self, name: str, **attrs) -> None:
        """Instant event (a point in time, not a duration)."""
        if not self.enabled:
            return
        now = self.clock()
        self._append(
            {
                "ph": "i",
                "name": name,
                "t0": now,
                "t1": now,
                "tid": threading.get_ident(),
                "depth": len(self._stack()),
                "attrs": attrs or None,
            }
        )

    # async (long-running, cross-step) spans — request lifecycles
    def begin_async(self, cat: str, aid: Any, name: str, **attrs) -> None:
        self._async(cat, aid, name, "b", attrs)

    def instant_async(self, cat: str, aid: Any, name: str, **attrs) -> None:
        self._async(cat, aid, name, "n", attrs)

    def end_async(self, cat: str, aid: Any, name: str, **attrs) -> None:
        self._async(cat, aid, name, "e", attrs)

    def _async(self, cat: str, aid: Any, name: str, ph: str, attrs: Dict) -> None:
        if not self.enabled:
            return
        now = self.clock()
        self._append(
            {
                "ph": ph,
                "cat": cat,
                "id": aid,
                "name": name,
                "t0": now,
                "t1": now,
                "tid": threading.get_ident(),
                "depth": 0,
                "attrs": attrs or None,
            }
        )

    # --- read surface ----------------------------------------------------
    def spans(self, last: Optional[int] = None) -> List[Dict[str, Any]]:
        """Snapshot of the ring buffer (oldest first); ``last`` trims to the
        newest N records."""
        with self._lock:
            out = list(self._buf)
        return out[-last:] if last else out

    def open_spans(self) -> List[Dict[str, Any]]:
        """Spans currently in flight on ANY thread — the flight recorder's
        'what was it doing when it died' answer. Best-effort snapshot of
        the per-thread stacks (a span entering/exiting concurrently may be
        missed or doubled; fine for a postmortem)."""
        with self._lock:
            stacks = list(self._stacks.values())
        now = self.clock()
        return [
            {
                "name": s.name,
                "t0": s.t0,
                "elapsed_ms": (now - s.t0) * 1e3,
                "depth": s.depth,
                "attrs": s.attrs,
            }
            for st in stacks
            for s in list(st)
        ]

    def dropped(self) -> int:
        with self._lock:
            return max(0, self._total - len(self._buf))

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._total = 0

    def phase_summary(self) -> Dict[str, Dict[str, float]]:
        """Aggregate completed spans by name: count, total/mean/max ms.
        The bench's ``step_phase_ms`` breakdown and the monitor feed read
        this."""
        out: Dict[str, Dict[str, float]] = {}
        for rec in self.spans():
            if rec["ph"] != "X":
                continue
            ms = (rec["t1"] - rec["t0"]) * 1e3
            agg = out.get(rec["name"])
            if agg is None:
                out[rec["name"]] = {"count": 1, "total_ms": ms, "max_ms": ms}
            else:
                agg["count"] += 1
                agg["total_ms"] += ms
                if ms > agg["max_ms"]:
                    agg["max_ms"] = ms
        for agg in out.values():
            agg["mean_ms"] = agg["total_ms"] / agg["count"]
            agg["total_ms"] = round(agg["total_ms"], 4)
            agg["mean_ms"] = round(agg["mean_ms"], 4)
            agg["max_ms"] = round(agg["max_ms"], 4)
        return out

    def summary(self) -> Dict[str, Any]:
        return {
            "enabled": self.enabled,
            "spans": len(self._buf),
            "dropped": self.dropped(),
            "open": [s["name"] for s in self.open_spans()],
            "phases": self.phase_summary(),
        }

    # --- Chrome-trace (Perfetto) export ----------------------------------
    def export_chrome_trace(
        self, path: str, metrics: Optional["MetricsRegistry"] = None
    ) -> str:
        """Write the ring buffer as Trace Event Format JSON (the format
        ``chrome://tracing`` and https://ui.perfetto.dev load directly).
        This timeline is the host's ``perf_counter`` and holds **no device
        operation**: it is the one view of the request lifecycles (``b`` /
        ``n`` / ``e`` events), of ``add_span`` records and of the pure-host
        fleet router. Where the question is what the device did under a
        span, take a ``jax.profiler`` trace instead: ``span()`` also lands
        there (``Tracer.sink``), on the device's clock.
        Span times become microsecond offsets from the tracer's anchor;
        the wall-clock anchor and an optional metrics snapshot ride in
        ``otherData``. Returns the written path. The write is
        temp+rename-atomic so a concurrently-read file is never torn."""
        wall0, perf0 = self._anchor
        events: List[Dict[str, Any]] = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": os.getpid(),
                "args": {"name": "deepspeed_tpu"},
            }
        ]
        for rec in self.spans():
            ts = round((rec["t0"] - perf0) * 1e6, 3)
            ev: Dict[str, Any] = {
                "name": rec["name"],
                "ph": rec["ph"],
                "pid": os.getpid(),
                "tid": rec["tid"],
                "ts": ts,
            }
            if rec["ph"] == "X":
                ev["dur"] = round((rec["t1"] - rec["t0"]) * 1e6, 3)
            elif rec["ph"] == "i":
                ev["s"] = "t"  # thread-scoped instant
            elif rec["ph"] in ("b", "n", "e"):
                ev["cat"] = rec.get("cat", "async")
                ev["id"] = str(rec.get("id"))
            if rec.get("attrs"):
                ev["args"] = rec["attrs"]
            events.append(ev)
        payload: Dict[str, Any] = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "anchor_unix_time": wall0,
                "dropped_spans": self.dropped(),
            },
        }
        if metrics is not None:
            payload["otherData"]["metrics"] = metrics.snapshot()
        return _atomic_json_dump(path, payload)


NULL_TRACER = Tracer(max_spans=1, enabled=False)
"""Shared disabled tracer: a safe default argument so instrumented code
never branches on ``tracer is None``."""


def percentile_summary(values) -> Dict[str, float]:
    """``{count, mean, p50, p99}`` summary of a host-side sample
    (``{'count': 0}`` when empty) — linear interpolation, matching
    numpy's default percentile method. Lives here (stdlib-only, never
    imports jax or numpy) so BOTH the scheduler's per-tenant latency
    stats and the fleet router's merged stats share one definition —
    the router is a DS-R010 host-only module that cannot import the
    scheduler."""
    vals = sorted(float(v) for v in values)
    n = len(vals)
    if n == 0:
        return {"count": 0}

    def pct(q: float) -> float:
        if n == 1:
            return vals[0]
        pos = q / 100.0 * (n - 1)
        lo = int(pos)
        hi = min(lo + 1, n - 1)
        return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)

    return {
        "count": n,
        "mean": sum(vals) / n,
        "p50": pct(50.0),
        "p99": pct(99.0),
    }


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------
class Counter:
    """Monotonic counter."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._v = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self._v += n

    def snapshot(self) -> float:
        return self._v


class Gauge:
    """Last-write-wins scalar."""

    __slots__ = ("name", "_v", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._v = float(v)

    def snapshot(self) -> float:
        return self._v


# generic latency-ish bounds (unit-agnostic; default reads naturally as ms)
_DEFAULT_BUCKETS = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
    100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0,
)


class Histogram:
    """Fixed-bucket histogram with interpolated percentiles.

    Observations land in ``len(bounds)+1`` buckets (the last is the
    overflow). ``percentile`` walks the cumulative counts and linearly
    interpolates inside the landing bucket — exact min/max observed values
    clamp the ends, so p50/p99 are always within the observed range."""

    __slots__ = ("name", "bounds", "_counts", "_sum", "_count", "_min", "_max", "_lock")

    def __init__(self, name: str, buckets: Optional[Sequence[float]] = None):
        self.name = name
        self.bounds: Tuple[float, ...] = tuple(sorted(buckets or _DEFAULT_BUCKETS))
        if not self.bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self._counts = [0] * (len(self.bounds) + 1)
        self._sum = 0.0
        self._count = 0
        self._min = float("inf")
        self._max = float("-inf")
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        idx = bisect.bisect_left(self.bounds, v)
        with self._lock:
            self._counts[idx] += 1
            self._sum += v
            self._count += 1
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    def percentile(self, p: float) -> float:
        """Interpolated percentile in [0, 100]; 0.0 when empty."""
        with self._lock:
            total = self._count
            if total == 0:
                return 0.0
            counts = list(self._counts)
            lo_obs, hi_obs = self._min, self._max
        target = max(1.0, p / 100.0 * total)
        cum = 0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            if cum + c >= target:
                lo = self.bounds[i - 1] if i > 0 else lo_obs
                hi = self.bounds[i] if i < len(self.bounds) else hi_obs
                frac = (target - cum) / c
                val = lo + (hi - lo) * frac
                return min(max(val, lo_obs), hi_obs)
            cum += c
        return hi_obs

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            if self._count == 0:
                return {"count": 0}
            out = {
                "count": self._count,
                "sum": round(self._sum, 6),
                "mean": round(self._sum / self._count, 6),
                "min": self._min,
                "max": self._max,
            }
        out["p50"] = round(self.percentile(50), 6)
        out["p99"] = round(self.percentile(99), 6)
        return out


class MetricsRegistry:
    """Named metric store: get-or-create counters/gauges/histograms.
    Re-requesting a name returns the SAME instance; requesting it as a
    different kind raises (a silent shadow would split the series)."""

    def __init__(self):
        self._metrics: Dict[str, Any] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, kind, *args):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = kind(name, *args)
            elif not isinstance(m, kind):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, requested {kind.__name__}"
                )
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str, buckets: Optional[Sequence[float]] = None) -> Histogram:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = Histogram(name, buckets)
            elif not isinstance(m, Histogram):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, requested Histogram"
                )
            return m

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            items = list(self._metrics.items())
        out: Dict[str, Any] = {"counters": {}, "gauges": {}, "histograms": {}}
        for name, m in sorted(items):
            if isinstance(m, Counter):
                out["counters"][name] = m.snapshot()
            elif isinstance(m, Gauge):
                out["gauges"][name] = m.snapshot()
            else:
                out["histograms"][name] = m.snapshot()
        return out


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
class FlightRecorder:
    """Crash postmortem: the last K spans + open spans + metrics, dumped to
    a JSON file when the process dies.

    Three triggers, all opt-in via :meth:`install`:

    * ``atexit`` — a clean interpreter exit leaves a final dump (reason
      ``"exit"``).
    * signals — SIGTERM/SIGINT etc.: dump, then chain to the previous
      handler (so the preemption SIGTERM of a TPU slice still terminates).
    * the ``utils/chaos.py`` kill hook — fires BEFORE the chaos action
      (``ChaosKilled`` raise or the real ``os._exit(137)``), records a
      ``chaos.<point>`` event as the timeline's last entry, and dumps with
      the armed point named. Every fault-injection kill from the PR-8
      matrix therefore leaves a postmortem whose last span names the
      injection point.

    Dumps are temp+rename-atomic; repeated dumps overwrite (latest wins).
    """

    def __init__(
        self,
        tracer: Tracer,
        metrics: Optional[MetricsRegistry] = None,
        path: Optional[str] = None,
        dump_dir: Optional[str] = None,
        last_spans: int = 256,
    ):
        if path is None:
            dump_dir = dump_dir or "."
            path = os.path.join(dump_dir, f"flight_recorder_{os.getpid()}.json")
        self.tracer = tracer
        self.metrics = metrics
        self.path = path
        self.last_spans = int(last_spans)
        self._installed: List[Callable[[], None]] = []
        self._prev_handlers: Dict[int, Any] = {}
        self.dumps = 0

    # --- triggers --------------------------------------------------------
    def install(
        self,
        on_exit: bool = True,
        signals: Sequence[int] = (),
        chaos: bool = True,
    ) -> "FlightRecorder":
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        if on_exit:
            atexit.register(self._atexit_dump)
            self._installed.append(lambda: atexit.unregister(self._atexit_dump))
        for sig in signals:
            prev = _signal.signal(sig, self._signal_dump)
            self._prev_handlers[sig] = prev
        if chaos:
            from deepspeed_tpu.utils import chaos as chaos_mod

            chaos_mod.add_kill_hook(self._chaos_dump)
            self._installed.append(
                lambda: chaos_mod.remove_kill_hook(self._chaos_dump)
            )
        return self

    def uninstall(self) -> None:
        for undo in self._installed:
            try:
                undo()
            except Exception:
                pass
        self._installed.clear()
        for sig, prev in self._prev_handlers.items():
            try:
                _signal.signal(sig, prev)
            except Exception:
                pass
        self._prev_handlers.clear()

    def _atexit_dump(self) -> None:
        try:
            self.dump(reason="exit")
        except Exception:
            pass  # a failing postmortem must never mask the real exit

    def _signal_dump(self, signum, frame) -> None:
        try:
            self.tracer.event(f"signal.{signum}")
            self.dump(reason="signal", point=str(signum))
        except Exception:
            pass
        prev = self._prev_handlers.get(signum)
        if callable(prev):
            prev(signum, frame)
        elif prev != _signal.SIG_IGN:
            # SIG_DFL, or None (installed by non-Python code — unknowable,
            # so fail toward termination): restore the default disposition
            # and re-raise, never swallow a kill signal
            _signal.signal(signum, _signal.SIG_DFL)
            _signal.raise_signal(signum)

    def _chaos_dump(self, point: str, action: str) -> None:
        # the chaos event becomes the timeline's LAST entry: a postmortem
        # reader (and the test matrix) can match it to the armed point
        self.tracer.event(f"chaos.{point}", action=action)
        self.dump(reason="chaos", point=point)

    # --- the dump --------------------------------------------------------
    def dump(self, reason: str = "manual", point: Optional[str] = None) -> str:
        from deepspeed_tpu.utils import chaos as chaos_mod

        sched = chaos_mod.active()
        payload = {
            "reason": reason,
            "point": point,
            "pid": os.getpid(),
            "wall_time": time.time(),
            "dropped_spans": self.tracer.dropped(),
            "open_spans": self.tracer.open_spans(),
            "spans": self.tracer.spans(last=self.last_spans),
            "metrics": self.metrics.snapshot() if self.metrics else None,
            "chaos_fired": list(sched.fired_log) if sched is not None else [],
        }
        _atomic_json_dump(self.path, payload)
        self.dumps += 1
        return self.path


# ---------------------------------------------------------------------------
# the hub
# ---------------------------------------------------------------------------
class ObservabilityHub:
    """One merged observability surface per engine.

    Holds the engine's tracer + metrics and a dict of named stat sources
    (callables returning dicts — ``compile_stats``, ``analysis_report``,
    ``serve_stats``, ``checkpoint_stats``). :meth:`report` is what
    ``engine.observability()`` returns: the live timeline and metrics next
    to every registered surface, each guarded so one failing source never
    hides the others."""

    def __init__(self, tracer: Tracer, metrics: MetricsRegistry):
        self.tracer = tracer
        self.metrics = metrics
        self._sources: Dict[str, Callable[[], Any]] = {}
        self.flight_recorder: Optional[FlightRecorder] = None

    def add_source(self, name: str, fn: Callable[[], Any]) -> None:
        self._sources[name] = fn

    def report(self, exclude: Sequence[str] = ()) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "timeline": self.tracer.summary(),
            "metrics": self.metrics.snapshot(),
        }
        for name, fn in self._sources.items():
            if name in exclude:
                continue
            try:
                out[name] = fn()
            except Exception as e:  # surface, never mask the siblings
                out[name] = {"error": f"{type(e).__name__}: {e}"}
        return out

    def export_chrome_trace(self, path: str) -> str:
        return self.tracer.export_chrome_trace(path, metrics=self.metrics)

    def install_flight_recorder(
        self,
        path: Optional[str] = None,
        dump_dir: Optional[str] = None,
        last_spans: int = 256,
        on_exit: bool = True,
        signals: Sequence[int] = (),
        chaos: bool = True,
    ) -> FlightRecorder:
        if self.flight_recorder is not None:
            self.flight_recorder.uninstall()
        self.flight_recorder = FlightRecorder(
            self.tracer, self.metrics, path=path, dump_dir=dump_dir,
            last_spans=last_spans,
        ).install(on_exit=on_exit, signals=signals, chaos=chaos)
        return self.flight_recorder

    def monitor_events(self, step: int) -> List[Tuple[str, float, int]]:
        """The periodic monitor feed: phase means from the timeline plus
        every registered metric, as ``(name, value, step)`` events for
        ``MonitorMaster.write_events``."""
        events: List[Tuple[str, float, int]] = []
        for name, agg in sorted(self.tracer.phase_summary().items()):
            events.append((f"Trace/{name}/mean_ms", float(agg["mean_ms"]), step))
        snap = self.metrics.snapshot()
        for name, v in snap["counters"].items():
            events.append((f"Metrics/{name}", float(v), step))
        for name, v in snap["gauges"].items():
            events.append((f"Metrics/{name}", float(v), step))
        for name, h in snap["histograms"].items():
            if h.get("count"):
                events.append((f"Metrics/{name}/p50", float(h["p50"]), step))
                events.append((f"Metrics/{name}/p99", float(h["p99"]), step))
        return events
