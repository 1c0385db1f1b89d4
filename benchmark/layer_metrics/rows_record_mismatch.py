"""The guard of the readers that take a step's rows from outside: the number
of the slice's steps whose own record (``row_lens`` as a multiset of
``(q_len, kv_len)`` pairs, and ``mixed``, on ``serve.pack{seq}``:
``mixed_step_share.py``) differs from the entry of the driver's ``rows_log``
for the ``server_step`` annotation that holds their ``serve.enqueue{seq}``.
The seven kernel rooflines count bytes and operations from ``rows_log``,
which ``ServeSession._row`` derives before each call from the requests'
settled state; the record is what ``_pack`` sent to the kernel. 0 says the
two agree; anything else, that the rooflines read rows the device did not
run (``benchmark/tools/step_record_check.py`` prints the steps, both
records, and what kind of difference each is).

The one reader of the five that reads a ``server_step`` annotation and
``rows_log``, to compare: annotation k of the slice is entry k of the log
(``ServeSession.step`` writes one entry a call inside ``bench_slice``), and a
step belongs to the annotation that holds its enqueue, on the host's clock
alone. None without ``rows_log`` (an untraced run, a training cell) or
without a record."""

from benchmark import files


def compared(trace, counters, cell):
    """(record, the ``rows_log`` entry of the call that enqueued its step, or
    None where no annotation holds the enqueue) for every step with a record;
    None where there is nothing to compare."""
    found = files.load_module("layer_metrics", "mixed_step_share").records(trace, cell)
    log = counters.get("rows_log")
    if not found or log is None:
        return None
    calls = sorted(trace.host_spans("server_step"), key=lambda ev: ev.start)
    if len(calls) != len(log):
        raise ValueError(f"{len(calls)} server_step annotations in the slice but {len(log)} steps logged")
    out = []
    for r in found:
        enq = r.step.enqueue
        held = [entry for ev, entry in zip(calls, log) if ev.start <= enq.start and enq.end <= ev.end]
        out.append((r, held[0] if held else None))
    return out


def differs(record, entry) -> bool:
    return entry is None or bool(entry["mixed"]) != record.mixed or sorted(tuple(row) for row in entry["rows"]) != sorted(record.rows)


def value(trace, counters, cell):
    pairs = compared(trace, counters, cell)
    return sum(differs(r, entry) for r, entry in pairs) if pairs else None
