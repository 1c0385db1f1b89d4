"""A serving step by its own number, and the gap between two executions read
where it happens. Shared by the readers ``exec_gap_ms``,
``host_turnaround_ms``, ``enqueue_call_ms`` and ``run_ahead_share`` and by
``tools/exec_gaps.py``.

Since PR 36 every span of a serving step's life carries the step's ``seq``
(``serve.pack``, ``serve.dispatch`` > ``serve.enqueue``, the jitted call
alone, with the ``program`` it enqueues; ``serve.fetch``, the wait for it;
``serve.emit`` > ``serve.settle``, and ``drain=<reason>`` on the emit of a
settle the host could not run ahead of). So:

* the gap between two executions is read on the device's own line, from the
  whole executions of the programs the slice's ``serve.enqueue`` spans name;
* the host's turnaround is read on the host's own clock, from
  ``serve.fetch{seq = n}``'s end to ``serve.enqueue{seq = n + 1}``'s start;
* neither needs the two clocks aligned. ``trace.clock_shift`` is used for one
  thing: to pair execution with enqueue by time (steps lie a device step
  apart, the shift is known to about a millisecond) and CHECK that the device
  ran the program the span names and that no step is missing between two
  pairs. A trace that fails the check is a changed program or a broken
  reader, and raises.
* a host that stood still is neither (PR 44). The sandbox stalls for 8 to
  ~110 ms at a time, a few times a window, and a stall inside the jitted call
  puts an execution's start further than ``PAIR_REACH_S`` behind its
  ``serve.enqueue``. Such a step is not paired by time; where the paired
  numbers skip, the unpaired enqueues and the unpaired whole executions
  between the two paired neighbours are counted, and where they are equally
  many they are paired by order (``Step.by_order``), under the same check of
  the program. Only where the counts differ is a step's execution missing.
  At the slice's two ends there is no neighbour to count from, so the reach
  stands there and a late execution stays unpaired, as one whose enqueue lies
  outside the slice does. No median reads ``Step.execution``: the stalled
  step's long gap is among ``exec_gaps`` and its turnaround among
  ``turnarounds``, as a real gap should be, one sample each under a median.

A trace without ``serve.enqueue`` spans (a program before PR 36, a training
cell) has nothing of this: ``steps`` returns None and every reader says None.
"""

from __future__ import annotations

import bisect
import dataclasses
import statistics
from typing import Dict, List, Optional, Sequence

from benchmark import op_scopes
from benchmark.program_spans import Span
from benchmark.trace_reduce import Event

ENQUEUE, FETCH, EMIT, DISPATCH = "serve.enqueue", "serve.fetch", "serve.emit", "serve.dispatch"
PAIR_REACH_S = 5e-3  # how far an execution's start may lie from its enqueue's: ``align_clock``'s reach


@dataclasses.dataclass(frozen=True)
class Step:
    seq: int
    program: str
    enqueue: Span
    fetch: Optional[Span]  # the wait for this step, where it lies whole inside the slice
    drained: bool  # settled by a drain: what follows it is no turnaround
    ahead: bool  # enqueued with the step before it unsettled (``serve.dispatch``'s ``ahead``; a window has none)
    execution: Optional[Event] = None  # the whole execution paired with it
    by_order: bool = False  # paired by order between two pairs by time: the execution started beyond the reach (a host stall)


def _consecutive(seqs: Sequence[int], what: str) -> None:
    for a, b in zip(seqs, seqs[1:]):
        if b != a + 1:
            raise ValueError(f"{what}: seq {a} is followed by {b}; a step's spans are missing from the slice's middle")


def _check_program(span: Span, m: Event) -> None:
    module = op_scopes.module_of(m.name)[0]
    if module != "jit_" + span.attrs["program"]:
        raise ValueError(f"seq {span.attrs['seq']}: serve.enqueue names {span.attrs['program']!r} and the device ran {module!r} {1e3 * (m.start - span.start):+.3f} ms from it")


def executions(trace, programs) -> List[Event]:
    """Device 0's whole executions of the named programs, by start."""
    modules = {"jit_" + p for p in programs}
    return sorted((m for m in trace.devices[0].whole_modules if op_scopes.module_of(m.name)[0] in modules), key=lambda m: m.start)


def steps(trace, spans: Sequence[Span]) -> Optional[List[Step]]:
    """The slice's steps by ``seq``, each with its enqueue, its wait and its
    execution, paired by time or, between two such pairs, by order; None where
    the trace holds no ``serve.enqueue``. Raises where the spans' numbers
    skip, where two executions fall to one enqueue, where the device ran
    another program than the span names, or where fewer or more executions
    than enqueues lie unpaired between two pairs."""
    enqueues = sorted((s for s in spans if s.name == ENQUEUE), key=lambda s: s.start)
    if not enqueues:
        return None
    _consecutive([s.attrs["seq"] for s in enqueues], "serve.enqueue spans")
    fetch = {s.attrs["seq"]: s for s in spans if s.name == FETCH and "seq" in s.attrs}
    drained = {s.attrs["seq"] for s in spans if s.name == EMIT and "drain" in s.attrs}
    ahead = {s.attrs["seq"] for s in spans if s.name == DISPATCH and s.attrs.get("ahead") == 1}
    starts = [s.start for s in enqueues]
    runs = executions(trace, {s.attrs["program"] for s in enqueues})
    paired: Dict[int, int] = {}  # seq -> index into ``runs``
    for k, m in enumerate(runs):
        i = bisect.bisect_left(starts, m.start)
        near = min((j for j in (i - 1, i) if 0 <= j < len(starts)), key=lambda j: abs(starts[j] - m.start))
        if abs(starts[near] - m.start) > PAIR_REACH_S:
            continue  # its enqueue lies outside the slice, or the host stood still between the two
        span = enqueues[near]
        seq = span.attrs["seq"]
        _check_program(span, m)
        if seq in paired:
            raise ValueError(f"seq {seq}: two executions of {op_scopes.module_of(m.name)[0]!r} fall to one serve.enqueue")
        paired[seq] = k
    by_seq = {s.attrs["seq"]: s for s in enqueues}
    by_order = set()
    by_time = sorted(paired)
    for a, b in zip(by_time, by_time[1:]):
        between = range(paired[a] + 1, paired[b])  # the unpaired executions between the two pairs; no pair by time lies among them
        if len(between) != b - a - 1:
            raise ValueError(
                f"executions paired with serve.enqueue spans: seq {a} is followed by {b}, and between the two lie {b - a - 1} "
                f"serve.enqueue span(s) and {len(between)} whole execution(s): a step's execution is missing from the slice's middle")
        for seq, k in zip(range(a + 1, b), between):
            _check_program(by_seq[seq], runs[k])
            paired[seq] = k
            by_order.add(seq)
    found = []
    for s in enqueues:
        seq = s.attrs["seq"]
        found.append(Step(seq, s.attrs["program"], s, fetch.get(seq), seq in drained, seq in ahead, runs[paired[seq]] if seq in paired else None, seq in by_order))
    return found


def exec_gaps(trace, found: Sequence[Step]) -> List[float]:
    """Seconds from one whole execution's end to the next one's start, over
    every consecutive pair of the slice: the device's clock alone."""
    runs = executions(trace, {st.program for st in found})
    return [b.start - a.end for a, b in zip(runs, runs[1:])]


def turnarounds(found: Sequence[Step]) -> Dict[int, float]:
    """By the later step's ``seq``: seconds from the return of step n's wait
    to the start of step n + 1's jitted call, where n + 1 ran ahead and no
    drain settled n (what ``serve.turnaround_ms`` observes): the host's clock
    alone."""
    return {
        b.seq: b.enqueue.start - a.fetch.end
        for a, b in zip(found, found[1:])
        if a.fetch is not None and not a.drained and b.ahead
    }


def median_ms(seconds) -> Optional[float]:
    seconds = list(seconds)
    return 1e3 * statistics.median(seconds) if seconds else None
