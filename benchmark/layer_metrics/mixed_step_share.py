"""Share of the slice's serving steps that ran the mixed program (width =
the prefill chunk: at least one row prefills), counted from the steps' own
records: ``mixed = 1`` on ``serve.pack{seq}`` over every step whose whole
execution is paired with its ``serve.enqueue{seq}``. Says whether two slices
held the same mix, before any share, roofline or idle share of theirs is
compared, and is the base of the arithmetic on what a mixed step costs over a
narrow one.

This file also holds what the five readers of a step's own record share
(``narrow_exec_ms``, ``mixed_exec_ms``, ``kv_tokens_per_step``,
``rows_record_mismatch`` load it by name): ``records``. Since PR 54
``scheduler.py`` puts on ``serve.pack``, under the step's ``seq``, what it
sends to the kernel: ``mixed`` (0 / 1), ``kv_tokens`` (the live rows' keys
once the step's own are written) and ``row_lens`` (the live rows' ``(q_len,
kv_len)`` in pack order, one word a row, ``kv`` alone where ``q`` is 1). A
step is packed in one call, enqueued in the next and settled in the one
after, and packed AGAIN under the same ``seq`` for a newcomer or after a
drain, so a step's record is its LAST ``serve.pack{seq}`` that ended before
``serve.enqueue{seq}`` began (one thread, the host's clock alone). The steps
are ``step_seq.steps``': an execution paired with its enqueue by time or by
order, the program checked. Left out: a step whose execution is unpaired or
cut by the slice's or the trace's end, and one whose pack lies before the
slice. No ``server_step`` annotation, no ``rows_log``, and ``clock_shift``
only inside ``step_seq``'s own pairing: a call that returns while its
execution runs changes nothing here. None without ``serve.enqueue`` spans or
without ``mixed`` on the packs (a program before PR 54, a training cell)."""

import dataclasses
from typing import List, Optional, Tuple

from benchmark import program_spans, step_seq

PACK = "serve.pack"


@dataclasses.dataclass(frozen=True)
class Record:
    step: step_seq.Step  # with its whole execution
    mixed: bool
    kv_tokens: int
    rows: Tuple[Tuple[int, int], ...]  # (q_len, kv_len) of the live rows, in pack order


def decode_row_lens(text: str) -> Tuple[Tuple[int, int], ...]:
    """``"513 128:256"`` -> ``((1, 513), (128, 256))``."""
    return tuple(tuple(int(n) for n in word.split(":")) if ":" in word else (1, int(word)) for word in text.split())


def records(trace, cell) -> Optional[List[Record]]:
    """The slice's steps that have both a whole execution and a record, by ``seq``."""
    if trace is None:
        return None
    spans = program_spans.of_cell(trace, cell)
    found = step_seq.steps(trace, spans)
    packs = {}  # seq -> its packs
    for s in spans:
        if s.name == PACK and "mixed" in s.attrs:
            packs.setdefault(s.attrs["seq"], []).append(s)
    if not found or not packs:
        return None
    out = []
    for st in found:
        before = [s for s in packs.get(st.seq, ()) if s.end <= st.enqueue.start]
        if st.execution is None or not before:
            continue
        attrs = max(before, key=lambda s: s.end).attrs
        out.append(Record(st, bool(attrs["mixed"]), int(attrs["kv_tokens"]), decode_row_lens(attrs["row_lens"])))
    return out


def exec_ms(trace, cell, mixed: bool) -> Optional[float]:
    """Median length of the paired whole executions of one kind of step, in milliseconds."""
    found = records(trace, cell)
    return step_seq.median_ms(r.step.execution.duration for r in found if r.mixed == mixed) if found else None


def value(trace, counters, cell):
    found = records(trace, cell)
    return 100.0 * sum(r.mixed for r in found) / len(found) if found else None
