"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read. This file is the yardstick: every PR reduces a trace the same
way, and no PR that claims a gain can change how.

Two halves:

* interval arithmetic on plain ``(start, end)`` pairs, checked by hand in
  ``tests/benchmark/test_trace_reduce.py``: union length, clipping, the
  leaf events of a nested line, self time per name, and the part of one set
  of intervals that no interval of another set covers;
* ``reduce_xplane``: reads the file with ``jax.profiler.ProfileData`` (JAX
  alone, no TensorFlow), takes the device planes' operation lines and the
  host threads' ``TraceAnnotation`` events, cuts everything to the slice the
  benchmark marked with its ``bench_slice`` annotation, and returns a
  ``ReducedTrace``.

How this runtime names things (traces read by hand with
``tools/inspect_trace.py``, PR 22, see PERF.md section 5): device planes are
``/device:TPU:<n>``; their line ``XLA Ops`` holds one event per executed HLO
instruction, **named by the instruction's whole HLO text** (``%closed_call.71
= (bf16[96,1024,64]{...}, ...) custom-call(...), custom_call_target=
"tpu_custom_call"``), so an operation is told by its opcode and a Pallas
kernel only by its signature; a ``while`` event spans its body's events,
hence the leaf/self-time arithmetic. ``Async XLA Ops`` holds one event per
asynchronous operation from its start to its done; ``XLA Modules`` one event
per program execution (``jit_fused_step(<fingerprint>)``). The benchmark's
``TraceAnnotation``s sit on the ``python3`` line of ``/host:CPU``. Times are
nanoseconds, but the device's clock ran about 1.2 ms behind the host's in
the traces read (an execution is recorded before the dispatch that started
it), so ``align_clock`` finds the shift under which the device's work falls
inside the host spans that wait for it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]

SLICE_ANNOTATION = "bench_slice"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"  # one event per executed HLO instruction
ASYNC_LINE = "Async XLA Ops"  # one event per async operation, start to done
MODULE_LINE = "XLA Modules"
# HLO collective opcodes, sync or the halves of async ones
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)(-start|-done)?$"
)
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<rest>.*)$", re.S)
_OPCODE = re.compile(r"[\)\}\]] (?P<op>[a-z][a-z0-9_-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def opcode(event_name: str) -> str:
    """The HLO opcode of an event named by its instruction's text
    (``%x.1 = bf16[8]{0} fusion(...)`` -> ``fusion``); for a plain name
    (``all-gather.3``) the name without its number."""
    m = _HLO.match(event_name)
    if m:
        op = _OPCODE.search(m.group("rest"))
        if op:
            return op.group("op")
    return re.sub(r"[.\d]+$", "", event_name.lstrip("%"))


def is_collective(event_name: str) -> bool:
    op = opcode(event_name)
    if COLLECTIVE.match(op):
        return True
    # an async wrapper around a collective computation
    return op in ("async-start", "async-done") and bool(
        re.search(r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)", event_name)
    )


def short_name(event_name: str, limit: int = 120) -> str:
    """``%name opcode output-shapes`` without layouts, for the breakdown."""
    m = _HLO.match(event_name)
    if not m:
        return event_name[:limit]
    rest = _LAYOUT.sub("", m.group("rest"))
    op = _OPCODE.search(m.group("rest"))
    shapes = rest.split(f" {op.group('op')}(")[0] if op else ""
    return f"%{m.group('name')} {op.group('op') if op else ''} {shapes}"[:limit]


# ---------------------------------------------------------------------------
# interval arithmetic


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same points."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    """Length of the union."""
    return sum(e - s for s, e in union(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The part of ``union(a)`` that no interval of ``b`` covers."""
    out: List[Interval] = []
    cover = union(b)
    j = 0
    for s, e in union(a):
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of ``[lo, hi]``."""
    return subtract([(lo, hi)], busy)


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds on the profiler's clock
    end: float
    line: str = ""

    @property
    def duration(self) -> float:
        return self.end - self.start


def leaf_and_self(events: Sequence[Event]) -> Tuple[List[Event], Dict[int, float]]:
    """For the events of ONE line, where an event may contain others (a
    ``while`` around its body): the leaves (events containing no other) and
    every event's self time (its length minus its direct children's), keyed
    by position in ``events``."""
    order = sorted(range(len(events)), key=lambda i: (events[i].start, -events[i].end))
    self_time = {i: events[i].duration for i in range(len(events))}
    has_child = set()
    stack: List[int] = []
    for i in order:
        ev = events[i]
        while stack and events[stack[-1]].end <= ev.start:
            stack.pop()
        if stack and ev.end <= events[stack[-1]].end:
            parent = stack[-1]
            has_child.add(parent)
            self_time[parent] -= ev.duration
        stack.append(i)
    leaves = [events[i] for i in range(len(events)) if i not in has_child]
    return leaves, self_time


def self_time_by_name(events: Sequence[Event]) -> Dict[str, float]:
    _, self_time = leaf_and_self(events)
    out: Dict[str, float] = {}
    for i, t in self_time.items():
        out[events[i].name] = out.get(events[i].name, 0.0) + max(t, 0.0)
    return out


# ---------------------------------------------------------------------------
# the reduced trace


@dataclasses.dataclass
class DeviceTrace:
    ordinal: int
    ops: List[Event]  # every op event cut to the slice (containers included)
    leaves: List[Event]  # ops that contain no other op
    modules: List[Event]
    async_ops: List[Event]
    busy: List[Interval]  # union of ops
    whole_modules: List[Event] = dataclasses.field(default_factory=list)  # executions the slice does not cut

    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy)

    def kernel_events(self, pattern: str) -> List[Event]:
        """Leaf events whose name, with the layouts (``{...}``) taken out,
        matches ``pattern``: a ``benchmark/kernels/*.py`` ``EVENTS`` entry."""
        rx = re.compile(pattern)
        return [ev for ev in self.leaves if "custom-call" in ev.name and rx.search(_LAYOUT.sub("", ev.name))]

    def checked_kernel_events(self, patterns: Dict[str, str], calls: Dict[str, int]) -> Dict[str, List[Event]]:
        """``kernel_events`` for each kind of ``patterns``, after a count
        check: every program execution that lies whole inside the slice and
        holds any of these events must hold exactly ``calls[kind]`` of each
        kind, and at least one execution must hold them. The kernels are
        told by their signatures only, so a kernel that changes its
        operands, or a new one of the same shape, has to fail here and not
        read as a gain."""
        found = {kind: self.kernel_events(pattern) for kind, pattern in patterns.items()}
        held = 0
        for m in self.whole_modules:
            counts = {kind: sum(1 for ev in evs if m.start <= ev.start and ev.end <= m.end) for kind, evs in found.items()}
            if any(counts.values()):
                held += 1
                if counts != calls:
                    raise ValueError(f"device {self.ordinal}: {m.name[:40]} holds kernel calls {counts}, the model needs {calls}: correct the kernel's EVENTS signatures")
        if not held:
            raise ValueError(f"device {self.ordinal}: no whole execution in the slice holds a kernel event of {sorted(patterns)}: correct the kernel's EVENTS signatures")
        return found

    def collective_intervals(self) -> List[Interval]:
        """Collective instructions on the op line, and async collectives
        from their start to their done."""
        return [(ev.start, ev.end) for ev in self.leaves + self.async_ops if is_collective(ev.name)]


@dataclasses.dataclass
class ReducedTrace:
    lo: float  # the slice, seconds on the profiler's clock
    hi: float
    devices: List[DeviceTrace]
    host: List[Event]  # the benchmark's own annotations, cut to the slice
    clock_shift: float = 0.0  # seconds added to device times

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def busy_s(self) -> float:
        """Mean over the devices of the time an operation ran."""
        return sum(d.busy_s() for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def host_spans(self, prefix: str) -> List[Event]:
        return [ev for ev in self.host if ev.name.startswith(prefix)]

    def busy_inside(self, span: Interval, device: int = 0) -> float:
        return total(clip(self.devices[device].busy, span[0], span[1]))

    def device_ops(self, top: int = 10) -> List[List]:
        """The operations that took most device time (self time, summed over
        executions, mean over devices)."""
        agg: Dict[str, float] = {}
        for d in self.devices:
            for name, t in self_time_by_name(d.ops).items():
                agg[name] = agg.get(name, 0.0) + t / len(self.devices)
        return [[short_name(n), t] for n, t in sorted(agg.items(), key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> List[List]:
        """Device 0's idle time by what the host was doing: every idle gap
        is split over the benchmark annotations that overlap it, the rest
        goes to ``host_other``. Summed per annotation name."""
        agg: Dict[str, float] = {}
        spans = sorted(self.host, key=lambda ev: ev.start)
        for gap in gaps(self.devices[0].busy, self.lo, self.hi):
            covered = 0.0
            for ev in spans:
                if ev.start >= gap[1]:
                    break
                part = overlap(gap, (ev.start, ev.end))
                if part > 0:
                    agg[ev.name] = agg.get(ev.name, 0.0) + part
                    covered += part
            rest = (gap[1] - gap[0]) - covered
            if rest > 0:
                agg["host_other"] = agg.get("host_other", 0.0) + rest
        return [[n, t] for n, t in sorted(agg.items(), key=lambda kv: -kv[1])[:top]]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _events_of(line, keep=None) -> List[Event]:
    out = []
    for ev in line.events:
        name = ev.name
        if keep is not None and not keep(name):
            continue
        start = ev.start_ns * 1e-9
        out.append(Event(name, start, start + ev.duration_ns * 1e-9, line.name))
    return out


def align_clock(busy: Sequence[Interval], spans: Sequence[Interval], reach: float = 5e-3, step: float = 2e-5) -> float:
    """The shift to add to device times so that the device's work falls
    inside ``spans``, host intervals that each dispatch device work and wait
    for it (a ``server.step()``). The busy time inside the spans, as a
    function of the shift, has a plateau where every execution is inside
    its span; the middle of the plateau is returned. 0.0 without spans."""
    if not spans or not busy:
        return 0.0
    edges = np.asarray([t for iv in busy for t in iv])
    cum = np.concatenate([[0.0], np.cumsum(np.diff(edges) * (np.arange(len(edges) - 1) % 2 == 0))])
    starts = np.asarray([s for s, _ in spans])
    ends = np.asarray([e for _, e in spans])
    shifts = np.arange(-reach, reach + step, step)
    inside = np.asarray([np.sum(np.interp(ends - d, edges, cum) - np.interp(starts - d, edges, cum)) for d in shifts])
    best = shifts[inside >= inside.max() - 1e-9 * max(inside.max(), 1.0)]
    return float(0.5 * (best[0] + best[-1]))


def reduce_xplane(path: str, annotations: Sequence[str], sync_annotations: Sequence[str] = ()) -> ReducedTrace:
    """Read one ``.xplane.pb``. ``annotations`` are the name prefixes of the
    benchmark's own ``TraceAnnotation``s; an annotation from any host thread
    counts. ``sync_annotations`` name those that wait for the device work
    they dispatch: the device clock is aligned on them. The slice is the
    ``bench_slice`` annotation; without one it is the span of the device
    events."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    wanted = tuple(annotations) + (SLICE_ANNOTATION,)
    raw_devices, host = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            by_line = {line.name: _events_of(line) for line in plane.lines if line.name in (OP_LINE, ASYNC_LINE, MODULE_LINE)}
            raw_devices.append((int(m.group(1)), by_line.get(OP_LINE, []), by_line.get(MODULE_LINE, []), by_line.get(ASYNC_LINE, [])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += _events_of(line, keep=lambda n: n.startswith(wanted))
    if not raw_devices:
        raise ValueError(f"{path}: no /device:TPU:<n> plane, so nothing ran on a chip while tracing")
    raw_devices.sort(key=lambda d: d[0])
    sync = [(ev.start, ev.end) for ev in host if ev.name.startswith(tuple(sync_annotations))] if sync_annotations else []
    shift = align_clock(union((ev.start, ev.end) for ev in raw_devices[0][1]), sorted(sync))
    slices = [ev for ev in host if ev.name == SLICE_ANNOTATION]
    if slices:
        lo, hi = slices[0].start, slices[0].end
    else:
        every = [ev for _, ops, _, _ in raw_devices for ev in ops]
        lo, hi = min(ev.start for ev in every) + shift, max(ev.end for ev in every) + shift

    def cut(events: List[Event], by: float = 0.0) -> List[Event]:
        return [
            Event(ev.name, max(ev.start + by, lo), min(ev.end + by, hi), ev.line)
            for ev in events
            if min(ev.end + by, hi) > max(ev.start + by, lo)
        ]

    devices = []
    for ordinal, ops, modules, async_ops in raw_devices:
        ops = cut(ops, shift)
        leaves, _ = leaf_and_self(ops)
        devices.append(
            DeviceTrace(
                ordinal=ordinal,
                ops=ops,
                leaves=leaves,
                modules=cut(modules, shift),
                async_ops=cut(async_ops, shift),
                busy=union((ev.start, ev.end) for ev in ops),
                whole_modules=[ev for ev in cut(modules, shift) if ev.start > lo and ev.end < hi],
            )
        )
    if not any(d.ops for d in devices):
        raise ValueError(f"{path}: no device operation inside the traced slice")
    host = [ev for ev in cut(host) if ev.name != SLICE_ANNOTATION]
    return ReducedTrace(lo=lo, hi=hi, devices=devices, host=host, clock_shift=shift)
