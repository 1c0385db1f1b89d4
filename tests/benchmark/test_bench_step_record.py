"""The five readers of a serving step's own record (PR 54:
``narrow_exec_ms``, ``mixed_exec_ms``, ``mixed_step_share``,
``kv_tokens_per_step``, ``rows_record_mismatch``;
``benchmark/layer_metrics/mixed_step_share.py`` has what they share): on
hand-made traces with known values, after ``test_bench_exec_gap.py``'s
pattern; on a small trace of the finished program recorded on a v5e chip
with the driver's own ``rows_log`` beside it
(``benchmark/tools/record_steprecord_trace.py``); and on the older recorded
traces, whose ``serve.pack`` has no ``mixed`` and where every reader has to
say None."""

import importlib.util
import json
import os
import statistics

import pytest

from benchmark import files, op_scopes, program_spans
from benchmark import trace_reduce as tr
from benchmark.program_spans import Span

HERE = os.path.dirname(__file__)
ROOT = os.path.join(HERE, "..", "..")
RECORDED = os.path.join(HERE, "data", "steprecord_tpu.xplane.pb")
ROWS_LOG = os.path.join(HERE, "data", "steprecord_rows_log.json")
RUNAHEAD = os.path.join(HERE, "data", "runahead_tpu.xplane.pb")
NAMED = os.path.join(HERE, "data", "named_tpu.xplane.pb")
READERS = ["narrow_exec_ms", "mixed_exec_ms", "mixed_step_share", "kv_tokens_per_step", "rows_record_mismatch"]
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}}
NARROW, MIXED = "paged_ragged_r16_w1", "paged_ragged_r16_w128"
MS = 1e-3
# one step of the hand-made server, in milliseconds: the jitted call, the launch behind its start, the two kinds'
# executions (a narrow one grows 0.01 ms a step), the wait's return behind the execution's end
CALL, LAUNCH, NARROW_MS, MIXED_MS, WAKE = 0.40, 0.35, 11.0, 23.0, 0.25


def reader(name):
    return files.load_module("layer_metrics", name)


def rows_of(i, mixed):
    """Step i's live rows as the kernel gets them: three decode rows a token longer each step and, in a mixed step, a chunk."""
    return [(1, 500 + i), (1, 90 + i), (1, 1200 + i)] + ([(128, 256)] if mixed else [])


def row_lens(rows):
    return " ".join(str(kv) if q == 1 else f"{q}:{kv}" for q, kv in rows)


def pack(t0, t1, seq, rows, mixed, record=True):
    attrs = {"seq": seq, "rows": len(rows), "width": 128 if mixed else 1, "program": MIXED if mixed else NARROW, "kv_pages": 7, "live_tokens": sum(q for q, _ in rows), "token_tiles": 0}
    if record:
        attrs.update(mixed=int(mixed), kv_tokens=sum(kv for _, kv in rows), row_lens=row_lens(rows))
    return Span("serve.pack", t0, t1, "python3", attrs)


def handmade(monkeypatch, n=9, mixed=(), cut_last=False, returns_early=False, repacked=(), record=True, first_seq=100):
    """``n`` calls of a server that runs one step ahead, on a host clock that
    starts at 1 s: call i enqueues step i (packed by the call before, but
    for the first), packs step i + 1 while the device runs and, unless
    ``returns_early``, ends with the wait for step i. A step in ``repacked``
    is packed AGAIN in the call that enqueues it, with a newcomer's chunk
    (what the call before packed held three decode rows alone): the last
    pack is the one the device ran. ``returns_early``: the call returns 2 ms
    after its enqueue and the NEXT call begins (``serve.step``,
    ``serve.admit``) and waits for the step while its execution still runs;
    ``cut_last``: the trace ended inside the last execution. Returns the
    trace, the rows log (an entry a call, equal to the records) and the
    expected narrow and mixed lengths."""
    spans, modules, host, log, narrow_ms, mixed_ms = [], [], [], [], [], []
    t = 1.0
    prev_end = None
    for i in range(n):
        seq = first_seq + i
        is_mixed = i in mixed or i in repacked
        rows = rows_of(i, is_mixed)
        call_start = t - 0.30 * MS
        if returns_early and prev_end is not None:
            # the call before returned long ago: this one opened, admitted, and waited for the step in flight
            call_start = prev_end - 8.0 * MS
            spans.append(Span("serve.admit", call_start + 0.02 * MS, call_start + 0.05 * MS, "python3", {"admitted": 0}))
            spans.append(Span("serve.fetch", call_start + 0.06 * MS, prev_end + WAKE * MS, "python3", {"seq": seq - 1}))
        if i == 0 or i in repacked:  # packed in this call, before its enqueue (a first step; a newcomer came)
            spans.append(pack(t - 0.25 * MS, t - 0.05 * MS, seq, rows, is_mixed, record))
        spans.append(Span("serve.dispatch", t - 0.01 * MS, t + (CALL + 0.1) * MS, "python3", {"seq": seq, "rows": len(rows), "width": 128 if is_mixed else 1, "program": MIXED if is_mixed else NARROW, "ahead": int(i > 0)}))
        spans.append(Span("serve.enqueue", t, t + CALL * MS, "python3", {"seq": seq, "program": MIXED if is_mixed else NARROW}))
        start = t + LAUNCH * MS
        length = MIXED_MS if is_mixed else NARROW_MS + 0.01 * i
        end = start + length * MS
        last = i == n - 1
        if not (last and cut_last):
            modules.append(tr.Event(f"jit_{MIXED if is_mixed else NARROW}(4711)", start, end, tr.MODULE_LINE))
            (mixed_ms if is_mixed else narrow_ms).append(length)
        if i:  # the step before is settled behind this one's enqueue
            spans.append(Span("serve.emit", t + 0.6 * MS, t + 1.1 * MS, "python3", {"seq": seq - 1}))
        if not last:  # the next step, packed while the device runs; a step that will be packed again holds no newcomer yet
            nxt_mixed = i + 1 in mixed
            spans.append(pack(t + 1.2 * MS, t + 1.6 * MS, seq + 1, rows_of(i + 1, nxt_mixed), nxt_mixed, record))
        if returns_early:
            call_end = t + 2.0 * MS  # the execution has 9 ms to go
        else:
            call_end = end + (WAKE + 0.05) * MS
            if not (last and cut_last):
                spans.append(Span("serve.fetch", t + 2.0 * MS, end + WAKE * MS, "python3", {"seq": seq}))
        spans.append(Span("serve.step", call_start + 0.01 * MS, call_end - 0.01 * MS, "python3", {"seq_enqueued": seq}))
        host.append(tr.Event("server_step", call_start, call_end, "python3"))
        log.append({"mixed": is_mixed, "rows": [list(r) for r in rows]})
        prev_end = end
        t = end + (WAKE + 0.15) * MS
    dev = tr.DeviceTrace(0, [], [], list(modules), [], [(m.start, m.end) for m in modules], whole_modules=list(modules))
    trace = tr.ReducedTrace(0.0, t + 1.0, [dev], host)
    monkeypatch.setattr(program_spans, "of_cell", lambda trace, cell: sorted(spans, key=lambda s: (s.start, -s.end)))
    return trace, log, narrow_ms, mixed_ms


def values(trace, log):
    return {name: reader(name).value(trace, {"rows_log": log}, CELL) for name in READERS}


def test_known_values(monkeypatch):
    trace, log, narrow_ms, mixed_ms = handmade(monkeypatch, mixed=(0, 4))
    got = values(trace, log)
    assert len(narrow_ms) == 7 and len(mixed_ms) == 2
    assert got["narrow_exec_ms"] == pytest.approx(statistics.median(narrow_ms)) == pytest.approx(NARROW_MS + 0.05)
    assert got["mixed_exec_ms"] == pytest.approx(MIXED_MS)
    assert got["mixed_step_share"] == pytest.approx(100.0 * 2 / 9)
    # three decode rows of 500 + i, 90 + i and 1200 + i keys, over the narrow steps 1, 2, 3, 5, 6, 7, 8
    assert got["kv_tokens_per_step"] == pytest.approx(1790 + 3 * statistics.mean([1, 2, 3, 5, 6, 7, 8]))
    assert got["rows_record_mismatch"] == 0
    found = reader("mixed_step_share").records(trace, CELL)
    assert [r.step.seq for r in found] == list(range(100, 109)) and [r.mixed for r in found] == [i in (0, 4) for i in range(9)]
    assert found[4].rows == ((1, 504), (1, 94), (1, 1204), (128, 256)) and found[4].kv_tokens == 504 + 94 + 1204 + 256
    assert all(op_scopes.module_of(r.step.execution.name)[0] == "jit_" + r.step.program for r in found)
    # the twins agree where a call waits for its execution, and only the twins read the wrapper
    assert reader("decode_step_device_ms").value(trace, {"rows_log": log}, CELL) == pytest.approx(got["narrow_exec_ms"])
    assert reader("mixed_step_device_ms").value(trace, {"rows_log": log}, CELL) == pytest.approx(got["mixed_exec_ms"])


def test_row_lens_come_back_as_pairs():
    share = reader("mixed_step_share")
    assert share.decode_row_lens("1:513 1:770 128:256") == ((1, 513), (1, 770), (128, 256))
    assert share.decode_row_lens("513 770 128:256 4:9") == ((1, 513), (1, 770), (128, 256), (4, 9))
    assert share.decode_row_lens("") == ()
    assert share.decode_row_lens(row_lens(rows_of(7, True))) == tuple(rows_of(7, True))


def test_a_step_cut_by_the_traces_end_is_left_out(monkeypatch):
    whole, log8, _, _ = handmade(monkeypatch, n=8, mixed=(2,))
    want = values(whole, log8)
    trace, log, narrow_ms, _ = handmade(monkeypatch, n=9, mixed=(2,), cut_last=True)
    assert len(trace.devices[0].whole_modules) == 8 and len(log) == 9
    got = values(trace, log)
    assert got == pytest.approx(want) and got["rows_record_mismatch"] == 0
    found = reader("mixed_step_share").records(trace, CELL)
    assert [r.step.seq for r in found] == list(range(100, 108))  # seq 108 has its enqueue and its pack, and no whole execution


def test_a_server_that_returns_while_the_device_runs_is_read_without_an_error(monkeypatch):
    """The server Queue 1 item 2 wants: a call returns 2 ms after its
    enqueue, the next call's host spans begin, and the execution is still
    running. The readers that time a step inside its ``server_step``
    annotation read 2 ms of an 11 ms step; the step's own record and its own
    execution read what the waiting server's do."""
    waiting, log, _, _ = handmade(monkeypatch, mixed=(0, 4))
    want = values(waiting, log)
    trace, log, narrow_ms, mixed_ms = handmade(monkeypatch, mixed=(0, 4), returns_early=True)
    calls = sorted(trace.host_spans("server_step"), key=lambda ev: ev.start)
    runs = trace.devices[0].whole_modules
    # every execution outlives the call that enqueued it, and the next call's spans begin inside it
    assert all(call.end < m.end for call, m in zip(calls, runs)) and all(nxt.start < m.end for nxt, m in zip(calls[1:], runs))
    got = values(trace, log)
    assert got == pytest.approx(want)
    assert got["narrow_exec_ms"] == pytest.approx(statistics.median(narrow_ms)) and got["mixed_exec_ms"] == pytest.approx(MIXED_MS) and got["rows_record_mismatch"] == 0
    # the outside twin sums the device's busy time inside the annotation: the tail of the step before and 2 ms of its own
    outside = reader("decode_step_device_ms").value(trace, {"rows_log": log}, CELL)
    assert outside < 0.9 * got["narrow_exec_ms"]


def test_a_repacked_seq_takes_its_last_pack(monkeypatch):
    """A newcomer makes the call pack the waiting step again under its
    ``seq``: the pack the call before made (three decode rows, narrow) is
    not what the device ran."""
    trace, log, narrow_ms, mixed_ms = handmade(monkeypatch, mixed=(0,), repacked=(5,))
    spans = program_spans.of_cell(trace, CELL)
    packs = [s for s in spans if s.name == "serve.pack" and s.attrs["seq"] == 105]
    assert [(p.attrs["mixed"], p.attrs["rows"]) for p in packs] == [(0, 3), (1, 4)]
    found = {r.step.seq: r for r in reader("mixed_step_share").records(trace, CELL)}
    assert found[105].mixed and found[105].rows == tuple(rows_of(5, True)) and found[105].kv_tokens == sum(kv for _, kv in rows_of(5, True))
    got = values(trace, log)
    assert got["mixed_step_share"] == pytest.approx(100.0 * 2 / 9) and got["rows_record_mismatch"] == 0
    assert got["mixed_exec_ms"] == pytest.approx(MIXED_MS) and got["narrow_exec_ms"] == pytest.approx(statistics.median(narrow_ms))


def test_a_mismatch_of_one_row_is_counted_as_one(monkeypatch):
    trace, log, _, _ = handmade(monkeypatch, mixed=(0, 4))
    log[3]["rows"][1][1] -= 1  # a decode row one key short in one step
    assert values(trace, log)["rows_record_mismatch"] == 1
    log[6]["rows"].append([1, 77])  # a row too many in another
    assert values(trace, log)["rows_record_mismatch"] == 2
    log[7]["rows"] = list(reversed(log[7]["rows"]))  # a multiset: the order is not compared
    log[8]["mixed"] = True  # the kind is
    assert values(trace, log)["rows_record_mismatch"] == 3
    guard = reader("rows_record_mismatch")
    differing = [r.step.seq for r, entry in guard.compared(trace, {"rows_log": log}, CELL) if guard.differs(r, entry)]
    assert differing == [103, 106, 108]
    with pytest.raises(ValueError, match="9 server_step annotations in the slice but 8 steps logged"):
        guard.value(trace, {"rows_log": log[:-1]}, CELL)


def test_the_tool_names_the_kind_of_a_mismatch(monkeypatch):
    spec = importlib.util.spec_from_file_location("step_record_check", os.path.join(ROOT, "benchmark", "tools", "step_record_check.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    trace, log, _, _ = handmade(monkeypatch, mixed=(0, 4))
    records = {r.step.seq: r for r in reader("mixed_step_share").records(trace, CELL)}
    # settled state a call early: every decode row one key short, and a row whose prompt ended in the step in flight
    assert tool.kind_of(records[102], {"mixed": False, "rows": [[1, 501], [1, 91], [0, 1201]]}) == "one_step_behind"
    assert tool.kind_of(records[102], {"mixed": False, "rows": [[1, 501], [1, 91], [1, 1201], [1, 640]]}) == "ended_rows"
    assert tool.kind_of(records[104], {"mixed": True, "rows": [[1, 503], [1, 93], [1, 1203], [128, 256]]}) == "one_step_behind"
    assert tool.kind_of(records[104], {"mixed": True, "rows": [[1, 503], [1, 93], [1, 1203], [64, 256]]}) == "other"
    assert tool.kind_of(records[102], {"mixed": True, "rows": log[2]["rows"]}) == "mixed_differs" and tool.kind_of(records[102], None) == "no_call"


@pytest.mark.parametrize("name", READERS)
def test_no_trace_no_enqueue_or_no_record_gets_none(monkeypatch, name):
    assert reader(name).value(None, {"rows_log": []}, CELL) is None  # a rehearsal
    # a program before PR 54: every span of a step's life, and no ``mixed`` on the pack
    trace, log, _, _ = handmade(monkeypatch, mixed=(0, 4), record=False)
    assert any(s.name == "serve.pack" for s in program_spans.of_cell(trace, CELL))
    assert reader(name).value(trace, {"rows_log": log}, CELL) is None
    # a program before PR 36: no ``serve.enqueue``
    trace, log, _, _ = handmade(monkeypatch, mixed=(0, 4))
    spans = [s for s in program_spans.of_cell(trace, CELL) if s.name != "serve.enqueue"]
    monkeypatch.setattr(program_spans, "of_cell", lambda trace, cell: spans)
    assert reader(name).value(trace, {"rows_log": log}, CELL) is None
    # an untraced run's counters hold no log: the guard has nothing to compare, the others do not ask
    trace, log, _, _ = handmade(monkeypatch, mixed=(0, 4))
    assert (reader(name).value(trace, {}, CELL) is None) == (name == "rows_record_mismatch")
    # a slice of narrow steps alone: no mixed length to report, and a share of 0
    trace, log, _, _ = handmade(monkeypatch)
    got = reader(name).value(trace, {"rows_log": log}, CELL)
    assert got == {"mixed_exec_ms": None, "mixed_step_share": 0.0, "rows_record_mismatch": 0}[name] if name in ("mixed_exec_ms", "mixed_step_share", "rows_record_mismatch") else got > 0


def test_none_of_the_five_reads_the_wrapper():
    """Only the guard may read a ``server_step`` annotation or ``rows_log``;
    none imports ``benchmark/serve_steps.py``, reads ``bench_slice`` or
    aligns a clock."""
    for name in READERS:
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")) as f:
            code = f.read().split('"""', 2)[2]  # what runs, not the words above it
        assert "serve_steps" not in code and "bench_slice" not in code and "clock_shift" not in code and "busy_inside" not in code, name
        assert ("server_step" in code or "rows_log" in code) == (name == "rows_record_mismatch"), name


# --- the recorded traces ----------------------------------------------------------


def recorded(monkeypatch, path):
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: path)
    return tr.reduce_xplane(path, ("submit", "server_step"), ("server_step",))


def rows_log():
    with open(ROWS_LOG) as f:
        return json.load(f)


def test_the_recorded_fixture_is_small_and_holds_the_record():
    assert os.path.getsize(RECORDED) < 300 * 1024
    packs = [s for s in program_spans.load(RECORDED) if s.name == "serve.pack"]
    assert packs and all({"seq", "mixed", "kv_tokens", "row_lens", "kv_pages", "live_tokens", "token_tiles"} <= set(s.attrs) for s in packs)
    assert not any({"table_pages", "latent_tokens"} & set(s.attrs) for s in packs)
    assert all(isinstance(s.attrs["row_lens"], str) for s in packs) and {s.attrs["mixed"] for s in packs} == {0, 1}
    # a seq packed twice: a newcomer came between the pack and the enqueue
    seqs = [s.attrs["seq"] for s in packs]
    assert len(seqs) > len(set(seqs))
    log = rows_log()
    assert len(log) == 30 and any(e["mixed"] for e in log) and not all(e["mixed"] for e in log)  # SLICE_CALLS of the recorder


def test_every_reader_reads_the_recorded_run(monkeypatch):
    trace = recorded(monkeypatch, RECORDED)
    log = rows_log()
    share = reader("mixed_step_share")
    found = share.records(trace, CELL)
    spans = program_spans.of_cell(trace, CELL)
    enqueues = [s for s in spans if s.name == "serve.enqueue"]
    # a call, a step enqueued; the first was packed before the slice began, and has no record inside it
    assert len(enqueues) == len(log) == 30 and [r.step.seq for r in found] == [s.attrs["seq"] for s in enqueues[1:]]
    got = {name: reader(name).value(trace, {"rows_log": log}, CELL) for name in READERS}
    mixed = [r for r in found if r.mixed]
    narrow = [r for r in found if not r.mixed]
    assert (len(narrow), len(mixed)) == (20, 9) and got["mixed_step_share"] == pytest.approx(100.0 * 9 / 29)
    assert got["rows_record_mismatch"] == 29 and got["kv_tokens_per_step"] == pytest.approx(405.45)
    # by hand, from the module line: an execution's own length, by the kind its pack names
    assert got["narrow_exec_ms"] == pytest.approx(1e3 * statistics.median(r.step.execution.duration for r in narrow))
    assert got["mixed_exec_ms"] == pytest.approx(1e3 * statistics.median(r.step.execution.duration for r in mixed))
    assert 1.0 < got["narrow_exec_ms"] < got["mixed_exec_ms"] < 40.0
    assert {op_scopes.module_of(r.step.execution.name)[0] for r in mixed} == {"jit_paged_ragged_r4_w128"}
    assert {op_scopes.module_of(r.step.execution.name)[0] for r in narrow} == {"jit_paged_ragged_r4_w1"}
    assert got["kv_tokens_per_step"] == pytest.approx(statistics.mean(r.kv_tokens for r in narrow))
    for r in found:  # the record is of one piece: the rows' keys are the step's, a narrow step's rows decode
        assert r.kv_tokens == sum(kv for _, kv in r.rows) and 1 <= len(r.rows) <= 4
        assert r.mixed == any(q > 1 for q, _ in r.rows)
    # the kind the pack names is the kind the driver logged (a delta of ``prefill_chunks`` inside the call) ...
    pairs = reader("rows_record_mismatch").compared(trace, {"rows_log": log}, CELL)
    assert len(pairs) == len(found) and all(entry is not None and bool(entry["mixed"]) == r.mixed for r, entry in pairs)
    # ... and the inside twins lie within a few percent of the outside ones, which sum the busy time inside the wrapper
    assert got["narrow_exec_ms"] == pytest.approx(reader("decode_step_device_ms").value(trace, {"rows_log": log}, CELL), rel=0.05)
    assert got["mixed_exec_ms"] == pytest.approx(reader("mixed_step_device_ms").value(trace, {"rows_log": log}, CELL), rel=0.05)


def test_the_outside_log_is_one_settle_behind_the_record(monkeypatch):
    """PR 54's first finding, on the recorded run: ``ServeSession._row``
    derives a call's rows before the call, from settled state, and the step
    enqueued the call before is not settled yet. So the log holds a decode
    row one key short of what the kernel reads, ``(0, n)`` for a row whose
    prompt ended in the step in flight, and a row whose budget ended there
    once more. The device ran the record: ``_pack`` made it from the arrays
    it sent (``tests/unit/inference/test_run_ahead.py`` holds it to the
    ``_Packed`` that was dispatched)."""
    spec = importlib.util.spec_from_file_location("step_record_check", os.path.join(ROOT, "benchmark", "tools", "step_record_check.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    trace = recorded(monkeypatch, RECORDED)
    log = rows_log()
    guard = reader("rows_record_mismatch")
    pairs = guard.compared(trace, {"rows_log": log}, CELL)
    kinds = [tool.kind_of(r, entry) for r, entry in pairs if guard.differs(r, entry)]
    # EVERY step of the slice: 23 with each decode row a key short, 6 that also hold a row whose budget had ended
    assert guard.value(trace, {"rows_log": log}, CELL) == len(kinds) == len(pairs) == 29
    assert (kinds.count("one_step_behind"), kinds.count("ended_rows")) == (23, 6)
    first, entry = pairs[0]
    assert first.rows == ((1, 156), (1, 15), (1, 47), (1, 97)) and entry["rows"] == [[1, 155], [1, 14], [1, 46], [1, 96]]
    ended, entry = pairs[3]  # the 47-key row's budget ended with its token in flight: packed no more, logged once more
    assert ended.rows == ((1, 159), (1, 18), (1, 100)) and entry["rows"] == [[1, 158], [1, 17], [1, 49], [1, 99]]
    # what the difference is worth to a reader that sums the rows' keys: a key a decode row, less than a percent here
    behind = [(r, entry) for r, entry in pairs if tool.kind_of(r, entry) == "one_step_behind" and guard.differs(r, entry)]
    assert all(0 < r.kv_tokens - sum(kv for q, kv in entry["rows"] if q) <= len(r.rows) for r, entry in behind if not any(q == 0 for q, _ in entry["rows"]))


@pytest.mark.parametrize("path", [RUNAHEAD, NAMED], ids=["seq_and_no_record", "named_by_pr23"])
@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_record_gets_none_and_no_error(monkeypatch, path, name):
    """What the driver's traced runs of the parent commit need."""
    trace = recorded(monkeypatch, path)
    assert reader(name).value(trace, {"rows_log": []}, CELL) is None


def test_the_nine_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    # four of the eight closed-loop cells: ``test_bench_{glm47_flash,laguna,kimi_linear,granite}.py`` hold their cell's family of
    # readers to a fixed set, and a PR that may edit no accepted file of the benchmark cannot lengthen it (PERF.md section 7)
    closed_loop = ["mistral7b_decode_heavy", "olmoe_decode_heavy", "solar_open2_decode_heavy", "mimo_v25_long_decode"]
    table = {
        "narrow_exec_ms": ("ms", "lower", "device_trace", "model"), "mixed_exec_ms": ("ms", "lower", "device_trace", "model"),
        "mixed_step_share": ("%", "lower", "program_span", "serving engine"), "kv_tokens_per_step": ("tokens", "lower", "program_span", "serving engine"),
        "rows_record_mismatch": ("steps", "lower", "program_span", "serving engine"),
    }
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for family, moves, cells in (("serve", "serve_tokens_per_s", closed_loop), ("chat", "itl_p50_ms", ["mistral7b_chat_steady"])):
        for r, (u, b, s, layer) in table.items():
            if (family, r) == ("chat", "mixed_exec_ms"):  # a 4 s chat slice may hold no mixed step; its outside twin is ``serve.`` only too
                assert "chat.mixed_exec_ms" not in by_name and "chat.mixed_step_device_ms" not in by_name
                continue
            m = by_name[f"{family}.{r}"]  # found by name: where it stands in the list is the next PR's business
            assert m == {"name": f"{family}.{r}", "unit": u, "better": b, "source": s, "layer": layer, "moves": moves, "workloads": cells}
    assert len([m for m in spec["per_layer"] if files.reader_of(m["name"]) in table]) == 9
    assert all(os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", r + ".py")) for r in table)
