"""The four readers PR 36 added (``exec_gap_ms``, ``host_turnaround_ms``,
``enqueue_call_ms``, ``run_ahead_share``; ``benchmark/step_seq.py``): on
hand-made traces with known gaps, on a small trace of the finished program
recorded on a v5e chip (``benchmark/tools/record_runahead_trace.py``: a paged
server that runs one step ahead, 36 calls of a two-layer model inside the
slice), and on the older recorded traces, whose program has no
``serve.enqueue`` span and where every reader has to say None."""

import dataclasses
import json
import os
import statistics

import pytest

from benchmark import files, op_scopes, program_spans, step_seq
from benchmark import trace_reduce as tr
from benchmark.program_spans import Span

HERE = os.path.dirname(__file__)
ROOT = os.path.join(HERE, "..", "..")
RUNAHEAD = os.path.join(HERE, "data", "runahead_tpu.xplane.pb")
NAMED = os.path.join(HERE, "data", "named_tpu.xplane.pb")
UNNAMED = os.path.join(HERE, "data", "small_tpu.xplane.pb")
READERS = ["exec_gap_ms", "host_turnaround_ms", "enqueue_call_ms", "run_ahead_share"]
CLOCK_FREE = ["exec_gap_ms", "host_turnaround_ms"]
CELL = {"name": "a_serving_cell", "config": {"engine": {"kind": "serve"}}}
NARROW, MIXED = "paged_ragged_r16_w1", "paged_ragged_r16_w128"
MS = 1e-3
# one step of the hand-made server, in milliseconds: the jitted call, the launch
# behind its start, the execution, the wait's return behind the execution's end
CALL, LAUNCH, DEVICE, WAKE = 0.40, 0.35, 11.0, 0.25


def reader(name):
    return files.load_module("layer_metrics", name)


def handmade(monkeypatch, n=9, drained=(), mixed=(), cut_last=False, clock_error=0.0, first_seq=100, rename=None, lose=None, skip_seq=None, late=None):
    """``n`` steps of a server that runs one step ahead, on a host clock that
    starts at 1 s. Step i's turnaround (the host between the return of the
    wait for step i - 1 and its own enqueue) is 0.10 + 0.01 i ms, so the gap
    on the device is WAKE + that + LAUNCH; a step behind a drained one comes
    5 ms late and not ahead. ``late[i]`` milliseconds is how long the host
    stood still inside step i's jitted call: the call is that much longer and
    the execution starts that much later. The device's events are
    ``clock_error`` seconds off the host's clock. Returns the trace, the
    turnarounds and the gaps."""
    late = late or {}
    spans, modules, turnarounds, gaps = [], [], [], []
    t = 1.0
    prev_end = None
    for i in range(n):
        seq = first_seq + i + (1 if skip_seq is not None and i >= skip_seq else 0)
        program = MIXED if i in mixed else NARROW
        behind_drain = i - 1 in drained
        if i:
            turn = 5.0 if behind_drain else 0.10 + 0.01 * i
            t += turn * MS
            if not behind_drain:
                turnarounds.append(turn)
        ahead = int(i > 0 and not behind_drain)
        stalled = late.get(i, 0.0)
        spans.append(Span("serve.dispatch", t - 0.01 * MS, t + (CALL + stalled + 0.1) * MS, "python3", {"seq": seq, "rows": 16, "width": 1, "program": program, "ahead": ahead}))
        spans.append(Span("serve.enqueue", t, t + (CALL + stalled) * MS, "python3", {"seq": seq, "program": program}))
        start = t + (LAUNCH + stalled) * MS
        end = start + DEVICE * MS
        if prev_end is not None:
            gaps.append((start - prev_end) / MS)
        prev_end = end
        last = i == n - 1
        if not (last and cut_last) and i != lose:
            name = f"jit_{rename[1] if rename and i == rename[0] else program}(4711)"
            modules.append(tr.Event(name, start - clock_error, end - clock_error, tr.MODULE_LINE))
        if i in drained:  # the step after could not be packed without this one's values: its settle holds the wait
            spans.append(Span("serve.emit", end - 8.0 * MS, end + (WAKE + 0.3) * MS, "python3", {"seq": seq, "drain": "preempt"}))
        if not (last and cut_last):
            spans.append(Span("serve.fetch", t + (2.0 + stalled) * MS if i not in drained else end - 7.9 * MS, end + WAKE * MS, "python3", {"seq": seq}))
        if i and not behind_drain:  # the step before is settled behind this one's enqueue, while the device runs
            spans.append(Span("serve.emit", t + (0.6 + stalled) * MS, t + (1.1 + stalled) * MS, "python3", {"seq": seq - 1}))
        t = end + WAKE * MS
    if cut_last:
        gaps.pop()
    if lose is not None:
        gaps = None
    hi = t + 1.0
    dev = tr.DeviceTrace(0, [], [], list(modules), [], [(m.start, m.end) for m in modules], whole_modules=list(modules))
    trace = tr.ReducedTrace(0.0, hi, [dev], [])
    monkeypatch.setattr(program_spans, "of_cell", lambda trace, cell: sorted(spans, key=lambda s: (s.start, -s.end)))
    return trace, turnarounds, gaps


def values(trace):
    return {name: reader(name).value(trace, {}, CELL) for name in READERS}


def test_known_gaps(monkeypatch):
    trace, turnarounds, gaps = handmade(monkeypatch, mixed=(0,))
    got = values(trace)
    assert got["exec_gap_ms"] == pytest.approx(statistics.median(gaps)) == pytest.approx(WAKE + LAUNCH + statistics.median(turnarounds))
    assert got["host_turnaround_ms"] == pytest.approx(statistics.median(turnarounds)) == pytest.approx(0.145)
    assert got["enqueue_call_ms"] == pytest.approx(CALL)
    assert got["run_ahead_share"] == pytest.approx(100.0 * 8 / 9)
    # what no reader times is what the subtraction leaves: the launch behind the call's start, and the wake-up
    assert got["exec_gap_ms"] - got["host_turnaround_ms"] - got["enqueue_call_ms"] == pytest.approx(LAUNCH - CALL + WAKE)
    found = step_seq.steps(trace, program_spans.of_cell(trace, CELL))
    assert [st.seq for st in found] == list(range(100, 109)) and [st.program for st in found] == [MIXED] + [NARROW] * 8
    assert all(st.execution is not None and op_scopes.module_of(st.execution.name)[0] == "jit_" + st.program for st in found)
    assert step_seq.turnarounds(found) == {100 + i: pytest.approx((0.10 + 0.01 * i) * MS) for i in range(1, 9)}


def test_a_drained_pair_is_left_out_of_the_turnaround_and_does_not_move_the_gap(monkeypatch):
    plain, _, _ = handmade(monkeypatch)
    want = values(plain)
    trace, turnarounds, gaps = handmade(monkeypatch, drained=(3,))
    got = values(trace)
    assert len(turnarounds) == 7 and max(gaps) == pytest.approx(WAKE + 5.0 + LAUNCH)
    assert got["host_turnaround_ms"] == pytest.approx(statistics.median(turnarounds))  # no 5 ms among them
    assert got["exec_gap_ms"] == pytest.approx(statistics.median(gaps)) == pytest.approx(want["exec_gap_ms"], abs=0.011)  # a median
    assert got["run_ahead_share"] == pytest.approx(100.0 * 7 / 9)  # the first step, and the one behind the drain
    found = step_seq.steps(trace, program_spans.of_cell(trace, CELL))
    assert [st.seq for st in found if st.drained] == [103] and 104 not in step_seq.turnarounds(found)
    # a server that fell back to the synchronous path: every step drained, nothing ahead
    sync, _, _ = handmade(monkeypatch, drained=tuple(range(9)))
    got = values(sync)
    assert got["run_ahead_share"] == 0.0 and got["host_turnaround_ms"] is None and got["exec_gap_ms"] > 5.0


def test_a_cut_execution_at_the_slices_end_is_left_out(monkeypatch):
    whole, _, _ = handmade(monkeypatch, n=8)
    trace, turnarounds, gaps = handmade(monkeypatch, n=9, cut_last=True)
    assert len(trace.devices[0].whole_modules) == 8 and len(gaps) == 7
    got = values(trace)
    assert got["exec_gap_ms"] == pytest.approx(values(whole)["exec_gap_ms"]) == pytest.approx(statistics.median(gaps))
    # its enqueue is whole inside the slice, so the host's side has one pair more
    assert got["host_turnaround_ms"] == pytest.approx(statistics.median(turnarounds)) and len(turnarounds) == 8
    assert step_seq.steps(trace, program_spans.of_cell(trace, CELL))[-1].execution is None


def test_a_mismatched_program_or_a_missing_step_raises(monkeypatch):
    # the span says the narrow program, the device ran the mixed one (another enqueue of the slice names it)
    trace, _, _ = handmade(monkeypatch, mixed=(0,), rename=(4, MIXED))
    for name in CLOCK_FREE:
        with pytest.raises(ValueError, match="serve.enqueue names 'paged_ragged_r16_w1' and the device ran 'jit_paged_ragged_r16_w128'"):
            reader(name).value(trace, {}, CELL)
    # an execution missing from the slice's middle: one enqueue between two pairs and no execution to give it
    trace, _, _ = handmade(monkeypatch, lose=4)
    for name in CLOCK_FREE:
        with pytest.raises(ValueError, match="seq 103 is followed by 105, and between the two lie 1 serve.enqueue span.s. and 0 whole execution.s.: a step's execution is missing"):
            reader(name).value(trace, {}, CELL)
    # the same beside a stalled step: two enqueues between two pairs and one execution
    trace, _, _ = handmade(monkeypatch, n=12, lose=4, late={5: 8.0})
    for name in CLOCK_FREE:
        with pytest.raises(ValueError, match="seq 103 is followed by 106, and between the two lie 2 serve.enqueue span.s. and 1 whole"):
            reader(name).value(trace, {}, CELL)
    # a stalled step whose execution is another program's than its span names: paired by order, and held to the same check
    trace, _, _ = handmade(monkeypatch, n=12, mixed=(0,), late={5: 8.0}, rename=(5, MIXED))
    for name in CLOCK_FREE:
        with pytest.raises(ValueError, match="seq 105: serve.enqueue names 'paged_ragged_r16_w1' and the device ran 'jit_paged_ragged_r16_w128'"):
            reader(name).value(trace, {}, CELL)
    # a number missing among the enqueues
    trace, _, _ = handmade(monkeypatch, skip_seq=5)
    for name in CLOCK_FREE:
        with pytest.raises(ValueError, match="serve.enqueue spans: seq 104 is followed by 106"):
            reader(name).value(trace, {}, CELL)
    # the two readers that read spans alone do not pair, and do not raise
    assert reader("enqueue_call_ms").value(trace, {}, CELL) == pytest.approx(CALL)


STALLS = {
    "one_execution_8_ms_late": ({5: 8.0}, [105], []),
    "three_in_a_row_after_a_110_ms_stall": ({4: 110.0, 5: 9.0, 6: 6.5}, [104, 105, 106], []),
    "two_stalls_apart": ({3: 8.0, 8: 20.0}, [103, 108], []),
    "a_late_one_at_the_slices_end": ({11: 8.0}, [], [111]),
    "a_late_one_at_the_slices_start": ({0: 8.0}, [], [100]),
    "late_at_both_ends_and_in_the_middle": ({0: 6.0, 6: 30.0, 11: 7.0}, [106], [100, 111]),
}


@pytest.mark.parametrize("case", sorted(STALLS))
def test_a_host_stall_is_not_a_broken_trace(monkeypatch, case):
    """A host that stood still inside the jitted call puts the execution's
    start beyond ``PAIR_REACH_S`` of its ``serve.enqueue``: no reader raises,
    the step is paired by order between its paired neighbours (at the slice's
    edge it stays unpaired), and each median keeps the stalled gap as ONE
    sample among its own, so it moves by a rank or two at the most."""
    late, by_order, unpaired = STALLS[case]
    quiet, _, _ = handmade(monkeypatch, n=12, mixed=(0,))
    want = values(quiet)
    assert not any(st.by_order for st in step_seq.steps(quiet, program_spans.of_cell(quiet, CELL)))
    trace, turnarounds, gaps = handmade(monkeypatch, n=12, mixed=(0,), late=late)
    if set(late) - {0}:  # no gap lies before the slice's first execution
        assert max(gaps) > 1e3 * step_seq.PAIR_REACH_S
    got = values(trace)
    # by hand: the stalled gaps are IN the sample, as real gaps should be; the turnarounds and the other calls are the quiet trace's
    assert got["exec_gap_ms"] == pytest.approx(statistics.median(gaps))
    assert got["host_turnaround_ms"] == pytest.approx(statistics.median(turnarounds)) == pytest.approx(want["host_turnaround_ms"])
    assert got["enqueue_call_ms"] == pytest.approx(want["enqueue_call_ms"]) == pytest.approx(CALL) and got["run_ahead_share"] == want["run_ahead_share"]
    # a step's gap grows by 0.01 ms a step in the hand-made server: the median moves by the stalled gaps' ranks, not by their size
    assert got["exec_gap_ms"] == pytest.approx(want["exec_gap_ms"], abs=0.01 * len(late) + 1e-9)
    found = step_seq.steps(trace, program_spans.of_cell(trace, CELL))
    assert [st.seq for st in found if st.by_order] == by_order and [st.seq for st in found if st.execution is None] == unpaired
    for st in found:
        if st.execution is not None:
            assert op_scopes.module_of(st.execution.name)[0] == "jit_" + st.program
            assert st.execution.start - st.enqueue.start == pytest.approx((LAUNCH + late.get(st.seq - 100, 0.0)) * MS)


@pytest.mark.parametrize("clock_error_ms", [-2.0, 2.0])
def test_a_constant_clock_error_moves_neither_clock_free_reader(monkeypatch, clock_error_ms):
    exact, _, _ = handmade(monkeypatch, mixed=(0, 3), drained=(5,))
    want = values(exact)
    off, _, _ = handmade(monkeypatch, mixed=(0, 3), drained=(5,), clock_error=clock_error_ms * MS)
    got = values(off)
    assert off.devices[0].whole_modules[0].start == pytest.approx(exact.devices[0].whole_modules[0].start - clock_error_ms * MS)
    for name in READERS:
        assert got[name] == pytest.approx(want[name], rel=1e-9)
    # and the check still pairs every execution with its own step
    found = step_seq.steps(off, program_spans.of_cell(off, CELL))
    assert all(st.execution is not None and op_scopes.module_of(st.execution.name)[0] == "jit_" + st.program for st in found)


@pytest.mark.parametrize("name", READERS)
def test_no_trace_or_no_enqueue_span_gets_none(monkeypatch, name):
    assert reader(name).value(None, {}, CELL) is None  # a rehearsal
    # the parent of PR 36: dispatches with ``ahead``, no ``serve.enqueue``
    trace, _, _ = handmade(monkeypatch)
    spans = [s for s in program_spans.of_cell(trace, CELL) if s.name != "serve.enqueue"]
    monkeypatch.setattr(program_spans, "of_cell", lambda trace, cell: spans)
    assert any("ahead" in s.attrs for s in spans) and reader(name).value(trace, {}, CELL) is None


# --- the recorded traces ----------------------------------------------------------


def recorded(monkeypatch, path):
    monkeypatch.setattr(tr, "find_xplane", lambda trace_dir: path)
    return tr.reduce_xplane(path, ("server_step",), ("server_step",))


@pytest.fixture
def runahead(monkeypatch):
    return recorded(monkeypatch, RUNAHEAD)


def test_the_recorded_fixture_is_small_and_holds_the_slice():
    assert os.path.getsize(RUNAHEAD) < 300 * 1024
    spans = program_spans.load(RUNAHEAD)
    assert {"serve.step", "serve.pack", "serve.dispatch", "serve.enqueue", "serve.fetch", "serve.emit", "serve.settle"} <= {s.name for s in spans}


def test_every_reader_reads_the_recorded_run(runahead):
    spans = program_spans.of_cell(runahead, CELL)
    found = step_seq.steps(runahead, spans)
    calls = [s for s in spans if s.name == "serve.step"]
    assert len(found) == len(calls) == 36  # SLICE_CALLS of the recorder: a call, a step enqueued
    assert [st.seq for st in found] == list(range(found[0].seq, found[0].seq + 36))
    assert [c.attrs["seq_enqueued"] for c in calls] == [st.seq for st in found]
    # the first step could not run ahead; every other did, and no drain lies inside the slice
    assert [st.ahead for st in found] == [False] + [True] * 35 and not any(st.drained for st in found)
    got = {name: reader(name).value(runahead, {}, CELL) for name in READERS}
    assert got["run_ahead_share"] == pytest.approx(100.0 * 35 / 36)
    # every execution whole inside the slice is paired with the step whose span names its program
    paired = [st for st in found if st.execution is not None]
    assert len(paired) >= 35 and len(paired) == len(step_seq.executions(runahead, {st.program for st in found}))
    assert {op_scopes.module_of(st.execution.name)[0] for st in paired} == {"jit_paged_ragged_r4_w128", "jit_paged_ragged_r4_w1"}
    # by hand, from the spans and the module line
    turn = [b.enqueue.start - a.fetch.end for a, b in zip(found, found[1:])]
    assert got["host_turnaround_ms"] == pytest.approx(1e3 * statistics.median(turn)) and min(turn) > 0
    runs = [st.execution for st in paired]
    assert got["exec_gap_ms"] == pytest.approx(1e3 * statistics.median(b.start - a.end for a, b in zip(runs, runs[1:])))
    assert got["enqueue_call_ms"] == pytest.approx(1e3 * statistics.median(st.enqueue.duration for st in found))
    # the device cannot start before the host has enqueued: the gap holds the turnaround and the synchronous part of the launch
    assert got["exec_gap_ms"] >= got["host_turnaround_ms"] + got["enqueue_call_ms"] - 0.05
    assert 0.02 < got["host_turnaround_ms"] < got["exec_gap_ms"] < 3.0 and 0.05 < got["enqueue_call_ms"] < 1.5
    # a step's wait ends after its execution does and its enqueue starts before: the clocks agree to within the aligner's width
    lower = max(st.enqueue.start - st.execution.start for st in paired)
    upper = min(st.fetch.end - st.execution.end for st in paired if st.fetch)
    assert lower < upper and upper - lower < 2e-3


def test_the_recorded_run_on_a_clock_a_millisecond_off(runahead):
    want = {name: reader(name).value(runahead, {}, CELL) for name in READERS}
    for off in (-1e-3, 1e-3):
        dev = runahead.devices[0]
        moved = dataclasses.replace(dev, whole_modules=[tr.Event(m.name, m.start + off, m.end + off, m.line) for m in dev.whole_modules])
        got = {name: reader(name).value(dataclasses.replace(runahead, devices=[moved]), {}, CELL) for name in READERS}
        assert got == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("path", [NAMED, UNNAMED], ids=["named_by_pr23", "unnamed"])
@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_span_gets_none_and_no_error(monkeypatch, path, name):
    """What the driver's traced runs of the parent commit need."""
    trace = recorded(monkeypatch, path)
    assert reader(name).value(trace, {}, CELL) is None


def test_the_eight_entries():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    decode = ["mistral7b_decode_heavy", "olmoe_decode_heavy", "solar_open2_decode_heavy", "mimo_v25_long_decode", "glm47_flash_long_decode"]
    table = {
        "exec_gap_ms": ("ms", "lower", "device_trace", "device"), "host_turnaround_ms": ("ms", "lower", "program_span", "serving engine"),
        "enqueue_call_ms": ("ms", "lower", "program_span", "serving engine"), "run_ahead_share": ("%", "higher", "program_span", "serving engine"),
    }
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for family, moves, cells in (("step", "serve_tokens_per_s", decode), ("chat", "itl_p50_ms", ["mistral7b_chat_steady"])):
        for r, (u, b, s, layer) in table.items():
            m = by_name[f"{family}.{r}"]  # found by name: where it stands in the list is the next PR's business
            assert {k: v for k, v in m.items() if k != "workloads"} == {"name": f"{family}.{r}", "unit": u, "better": b, "source": s, "layer": layer, "moves": moves}
            assert set(cells) <= set(m["workloads"])
    assert all(os.path.exists(os.path.join(ROOT, "benchmark", "layer_metrics", r + ".py")) for r in table)
