"""The program's own host spans, read from the run's profiler trace.

Since PR 23 every ``Tracer.span()`` of the program is also a
``jax.profiler.TraceAnnotation`` (``profiling/tracer.py``: ``Tracer.sink``),
so the spans sit on the host threads' lines of the same ``.xplane.pb`` as the
benchmark's own annotations and the device ops, on one clock, with their
attributes as the events' stats:

    serve.step {waiting, running, pages_in_use, pages_total}
      serve.admit {admitted}
      serve.pack {rows, width, program}
      serve.dispatch {rows, width, program}
      serve.emit > serve.fetch (the wait for the device), serve.settle {tokens}
    train.dispatch {program, step}    (and train.h2d, train.step_commit, ...)

The file is ``.benchmark_trace/<cell name>/`` where ``run.py`` wrote it; it
is parsed once per process. Spans are kept whole: one that the slice
``[trace.lo, trace.hi]`` cuts is left out. A trace of a program that
annotates nothing (the parent of PR 23) holds no such event, and every reader
then returns None.
"""

from __future__ import annotations

import dataclasses
import functools
import statistics
from typing import Any, Dict, List, Optional, Tuple

from benchmark import op_scopes, trace_reduce

PREFIXES = ("serve.", "train.", "eval.", "ckpt.", "fleet.")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float  # seconds on the profiler's clock
    end: float
    thread: str
    attrs: Dict[str, Any]

    @property
    def duration(self) -> float:
        return self.end - self.start


@functools.lru_cache(maxsize=4)
def load(path: str) -> Tuple[Span, ...]:
    """Every program span of one ``.xplane.pb``, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    start = ev.start_ns * 1e-9
                    out.append(Span(ev.name, start, start + ev.duration_ns * 1e-9, line.name, dict(ev.stats)))
    return tuple(sorted(out, key=lambda s: (s.start, -s.end)))


def of_cell(trace, cell: Dict) -> List[Span]:
    """The run's own program spans that lie whole inside the traced slice."""
    return [s for s in load(op_scopes.xplane_of(cell)) if s.start >= trace.lo and s.end <= trace.hi]


def self_seconds(spans: List[Span], name: str) -> List[float]:
    """For every span called ``name``: its length minus the part that other
    program spans of its thread inside it cover."""
    out = []
    for s in spans:
        if s.name != name:
            continue
        inner = [(c.start, c.end) for c in spans if c is not s and c.thread == s.thread and s.start <= c.start and c.end <= s.end]
        out.append(s.duration - trace_reduce.total(inner))
    return out


def phase_ms(trace, cell: Dict, phase: str) -> Optional[float]:
    """Median over the slice's steps of the self time of ``<kind>.<phase>``,
    in milliseconds, ``kind`` being the span family of the cell's engine
    (``serve`` or ``train``); None without a trace or without such a span."""
    if trace is None:
        return None
    seconds = self_seconds(of_cell(trace, cell), f"{cell['config']['engine']['kind']}.{phase}")
    return 1e3 * statistics.median(seconds) if seconds else None


def attr_values(trace, cell: Dict, name: str, *attrs: str) -> List[Tuple]:
    """The named attributes of every span called ``name`` that carries all
    of them."""
    found = [s.attrs for s in of_cell(trace, cell) if s.name == name]
    return [tuple(a[k] for k in attrs) for a in found if all(k in a for k in attrs)]
