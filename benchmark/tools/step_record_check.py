"""One traced run of a serving cell through ``run.py`` itself, then the five
readings a step's own record gives (``narrow_exec_ms``, ``mixed_exec_ms``,
``mixed_step_share``, ``kv_tokens_per_step``, ``rows_record_mismatch``:
``benchmark/layer_metrics/mixed_step_share.py``) beside their outside twins
(``decode_step_device_ms``, ``mixed_step_device_ms``, which time the step
inside the ``server_step`` annotation) and every step whose record differs
from the driver's ``rows_log`` (PERF.md section 5 has the table):

    python3 benchmark/tools/step_record_check.py --workload <cell> --seed <n> --seconds <s>

Prints one JSON line after ``run.py``'s own, ``{"step_record": ...}``: the
readings, ``step_pack_ms``, the steps of each kind, the tokens a second of
the measured window (no profiler session) and of the traced slice (a session
on: ``serve.settle``'s ``tokens`` over the slice's length), and the
mismatched steps counted by kind with the first few of each kind in full.
Every mismatched step goes, one JSON line each with its ``seq`` and both
records, to ``chiprun_out/step_record/<cell>.jsonl``. The kinds:

* ``one_step_behind``: the log's rows are the record's with a decode row's
  ``kv_len`` one short, or ``(0, kv_len - 1)`` for a row whose prompt ended
  in the step in flight: ``ServeSession._row`` reads the requests before the
  call, when the step enqueued the call before is not settled yet, so the
  log holds what the step BEFORE the one the device runs would read;
* ``ended_rows``: the same, and the log holds rows the record has not: a
  request whose budget ends with the token in flight is not packed again,
  while ``session.live`` keeps it until its settle a call later;
* ``mixed_differs``, ``no_call`` (no ``server_step`` annotation holds the
  step's enqueue), ``other``.

On a program before PR 54 the five readings are None and the line says so.
"""

from __future__ import annotations

import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

READERS = ("narrow_exec_ms", "decode_step_device_ms", "mixed_exec_ms", "mixed_step_device_ms", "mixed_step_share",
           "kv_tokens_per_step", "rows_record_mismatch", "step_pack_ms")
SHOWN = 3  # mismatched steps printed in full, of each kind


def kind_of(record, entry) -> str:
    """What kind of difference a mismatched step is (the module's docstring)."""
    if entry is None:
        return "no_call"
    if bool(entry["mixed"]) != record.mixed:
        return "mixed_differs"
    log = collections.Counter(tuple(row) for row in entry["rows"])
    for q, kv in sorted(record.rows):
        # a decode row as settled state knows it a call early: one key short, or still the prompt's last chunk, done. The
        # shortest row first and the row behind before the row itself, so that no row takes the entry of its neighbour
        for seen in ((1, kv - 1), (0, kv - 1), (q, kv)) if q == 1 else ((q, kv),):
            if log[seen]:
                log[seen] -= 1
                break
        else:
            return "other"
    return "ended_rows" if +log else "one_step_behind"


def main() -> int:
    from benchmark import run as bench

    kept = {}
    load_module = bench.load_module

    def keeping(kind, name):
        module = load_module(kind, name)
        if kind != "drivers":
            return module

        class Driver:
            @staticmethod
            def run(ctx):
                kept["ctx"], kept["result"] = ctx, module.run(ctx)
                return kept["result"]

        return Driver

    bench.load_module = keeping
    rc = bench.main(sys.argv[1:] + ["--trace", "1"])
    from benchmark import files, program_spans, trace_reduce

    ctx, result = kept["ctx"], kept["result"]
    trace = trace_reduce.reduce_xplane(trace_reduce.find_xplane(ctx.trace_dir), result["annotations"], result["sync_annotations"])
    cell = {"name": ctx.cell, "config": ctx.config, "traffic": ctx.traffic}
    counters = result["counters"]
    out = {"cell": ctx.cell, "seed": ctx.seed}
    for name in READERS:
        out[name] = files.load_module("layer_metrics", name).value(trace, counters, cell)
    out["window_tokens_per_s"] = files.load_module("end_to_end", "serve_tokens_per_s").value(result["window"], cell)
    settled = program_spans.attr_values(trace, cell, "serve.settle", "tokens")
    out["slice_tokens_per_s"] = sum(t for (t,) in settled) / trace.window_s
    guard = files.load_module("layer_metrics", "rows_record_mismatch")
    pairs = guard.compared(trace, counters, cell)
    if pairs is None:
        out["note"] = "no record on serve.pack: a program before PR 54"
    else:
        out["steps"] = {"narrow": sum(not r.mixed for r, _ in pairs), "mixed": sum(r.mixed for r, _ in pairs), "logged_calls": len(counters["rows_log"])}
        kinds, shown = collections.Counter(), collections.defaultdict(list)
        os.makedirs(os.path.join(ROOT, "chiprun_out", "step_record"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", "step_record", ctx.cell + ".jsonl"), "w") as f:
            for r, entry in pairs:
                if not guard.differs(r, entry):
                    continue
                kind = kind_of(r, entry)
                kinds[kind] += 1
                line = {"seq": r.step.seq, "kind": kind, "record": {"mixed": r.mixed, "rows": sorted(r.rows)},
                        "rows_log": entry and {"mixed": bool(entry["mixed"]), "rows": sorted(tuple(row) for row in entry["rows"])}}
                f.write(json.dumps(line) + "\n")
                if len(shown[kind]) < SHOWN:
                    shown[kind].append(line)
        out["mismatched"] = {"steps_compared": len(pairs), "by_kind": dict(kinds), "first": dict(shown)}
    print(json.dumps({"step_record": out}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
